"""Continuous-batching decode engine: the slot lifecycle (admission /
EOS / budget / max-len retirement), the join/leave-vs-alone oracle
equivalence, the fixed-shape compile-count acceptance, the zero-norm-work
decode jaxpr, per-row cache semantics, the arch rejection contracts, and
the 2-device subprocess mesh run.

Oracle contract: a request served MID-STREAM (joining a running batch,
sharing its decode step with strangers at other depths) must produce the
same greedy tokens as the same request served alone through
``generate()`` with the same adapter state — fp32-bitwise where the
grouped ≥2-row guarantee applies (single-handle slot tables run the
homogeneous gsB path; per-slot 1-row groups are allclose, see
docs/numerics.md).
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import AdapterStateCache, DoRAConfig
from repro.launch.engine import DecodeEngine
from repro.launch.serve import EngineServer, MultiTenantServer, Request, \
    generate
from repro.launch.steps import (StepConfig, make_decode_step,
                                make_draft_step,
                                make_prefill_into_slot_step,
                                make_verify_step)
from repro.launch.train import build_state
from repro.models import init_cache

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
ARCH = "qwen2-7b"


def _setup(tenants=1, dtype=None):
    mcfg = get_config(ARCH, smoke=True)
    if dtype is not None:
        mcfg = dataclasses.replace(mcfg, dtype=dtype)
    scfg = StepConfig(dora=DCFG)
    params, _, _ = build_state(mcfg, DCFG, 0)
    cache = AdapterStateCache.for_serving(mcfg, scfg)
    for t in range(tenants):
        _, ad, _ = build_state(mcfg, DCFG, 10 + t)
        cache.register(f"t{t}", ad)
    return mcfg, scfg, params, cache


def _perturb(adapters, seed, scale=0.1):
    """Non-identity variant of an adapter tree: inject random B leaves
    (A/m keep their seed values). Seed-built trees have B == 0, so every
    version would otherwise stream identical tokens — useless for
    distinguishing pinned-version from current-version. The mild default
    scale keeps the adapted model CLOSE to base: speculative drafts are
    then right sometimes and wrong sometimes, which is exactly what the
    oracle tests need (bitwise equality through real rejections)."""
    key = jax.random.PRNGKey(seed)
    cnt = [0]

    def f(path, leaf):
        cnt[0] += 1
        if "'B'" in "/".join(str(p) for p in path):
            return jax.random.normal(jax.random.fold_in(key, cnt[0]),
                                     leaf.shape, leaf.dtype) * scale
        return leaf
    return jax.tree_util.tree_map_with_path(f, adapters)


def _alone(mcfg, scfg, params, cache, prompt, gen_len, max_len, adapter):
    """The oracle: the same request served alone through generate()."""
    toks = np.asarray(generate(
        mcfg, params, cache.current_handle(adapter), scfg,
        np.asarray(prompt)[None], gen_len=gen_len, max_len=max_len,
        adapter_cache=cache))
    return toks[0, len(prompt):]


class TestSlotLifecycle:
    ML = 14

    def test_join_leave_oracle_equivalence(self):
        """ACCEPTANCE: 3 mixed-length requests through 2 slots — r1
        retires early, r2 joins the RUNNING batch — and every request's
        greedy tokens equal serving it alone through generate()."""
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=self.ML,
                          adapter_cache=cache)
        rng = np.random.default_rng(0)
        reqs = [(rng.integers(0, mcfg.vocab_size, P, dtype=np.int32), g)
                for P, g in [(5, 6), (6, 3), (4, 5)]]
        for p, g in reqs:
            eng.submit(p, adapter="t0", max_new_tokens=g)
        results = eng.run()
        assert [r.request_id for r in results] == [0, 1, 2]
        # r2 could only start after a retirement freed a slot
        assert results[2].admitted_step > results[1].finished_step \
            or results[2].admitted_step > results[0].finished_step
        for r, (p, g) in zip(results, reqs):
            assert r.finish_reason == "length"
            np.testing.assert_array_equal(
                r.tokens, _alone(mcfg, scfg, params, cache, p, g, self.ML,
                                 "t0"),
                err_msg=f"request {r.request_id} served mid-stream "
                        f"diverged from serving it alone")

    def test_streaming_and_prompt_roundtrip(self):
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=10,
                          adapter_cache=cache)
        rng = np.random.default_rng(1)
        p = rng.integers(0, mcfg.vocab_size, 5, dtype=np.int32)
        rid = eng.submit(p, adapter="t0", max_new_tokens=3)
        seen = []
        results = eng.run(on_token=lambda r, t: seen.append((r, t)))
        np.testing.assert_array_equal(results[0].prompt, p)
        assert seen == [(rid, int(t)) for t in results[0].tokens]

    def test_admission_under_full_slot_table(self):
        """5 requests, 2 slots: the table never overflows, admission is
        FIFO, every request completes, and the queue drains through
        retirements (prefills == admissions == 5)."""
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=10,
                          adapter_cache=cache)
        rng = np.random.default_rng(2)
        reqs = [(rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32),
                 2 + (i % 3)) for i in range(5)]
        for p, g in reqs:
            eng.submit(p, adapter="t0", max_new_tokens=g)
        results = eng.run()
        st = eng.stats()
        assert st.prefills == st.admitted == st.retired == 5
        assert not eng.has_work()
        # FIFO admission: request i is never admitted before request i-1
        admits = [r.admitted_step for r in results]
        assert admits == sorted(admits)
        # never more than `slots` rows active in one decode step
        assert st.slot_steps <= 2 * st.decode_steps
        for r, (p, g) in zip(results, reqs):
            np.testing.assert_array_equal(
                r.tokens, _alone(mcfg, scfg, params, cache, p, g, 10, "t0"))

    def test_eos_retirement_frees_slot_for_waiting_request(self):
        """A request retiring on EOS stops early AND hands its row to the
        queue; the late joiner still matches its oracle."""
        mcfg, scfg, params, cache = _setup()
        rng = np.random.default_rng(3)
        p0 = rng.integers(0, mcfg.vocab_size, 5, dtype=np.int32)
        ref = _alone(mcfg, scfg, params, cache, p0, 6, 14, "t0")
        eos = int(ref[2])            # a mid-stream greedy token as EOS
        stop = int(np.where(ref == eos)[0][0])   # earliest occurrence
        assert stop < len(ref) - 1, "eos must cut generation short"
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=14,
                          adapter_cache=cache)
        eng.submit(p0, adapter="t0", max_new_tokens=6, eos_id=eos)
        p1 = rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
        eng.submit(p1, adapter="t0", max_new_tokens=3)
        r0, r1 = eng.run()
        assert r0.finish_reason == "eos"
        np.testing.assert_array_equal(r0.tokens, ref[:stop + 1])
        assert r1.admitted_step > r0.finished_step
        np.testing.assert_array_equal(
            r1.tokens, _alone(mcfg, scfg, params, cache, p1, 3, 14, "t0"))

    def test_max_len_retirement_caps_generation(self):
        """A budget larger than the cache bound retires at max_len with
        exactly max_len - P tokens (the row never writes out of bounds)."""
        mcfg, scfg, params, cache = _setup()
        rng = np.random.default_rng(4)
        p = rng.integers(0, mcfg.vocab_size, 6, dtype=np.int32)
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=10,
                          adapter_cache=cache)
        eng.submit(p, adapter="t0", max_new_tokens=50)
        (r,) = eng.run()
        assert r.finish_reason == "max_len"
        assert r.tokens.shape == (4,)       # max_len - P
        np.testing.assert_array_equal(
            r.tokens, _alone(mcfg, scfg, params, cache, p, 4, 10, "t0"))

    def test_single_token_budget_never_occupies_a_decode_row(self):
        mcfg, scfg, params, cache = _setup()
        rng = np.random.default_rng(5)
        p = rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=8,
                          adapter_cache=cache)
        eng.submit(p, adapter="t0", max_new_tokens=1)
        (r,) = eng.run()
        assert r.tokens.shape == (1,) and r.finish_reason == "length"
        assert eng.stats().decode_steps == 0
        np.testing.assert_array_equal(
            r.tokens, _alone(mcfg, scfg, params, cache, p, 1, 8, "t0"))

    def test_submit_contracts(self):
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=6,
                          adapter_cache=cache)
        with pytest.raises(ValueError, match="P \\+ 1 <= max_len"):
            eng.submit(np.zeros(6, np.int32), adapter="t0",
                       max_new_tokens=2)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros(3, np.int32), adapter="t0",
                       max_new_tokens=0)
        with pytest.raises(ValueError, match="adapter id or handle"):
            eng.submit(np.zeros(3, np.int32), max_new_tokens=2)


class TestCompiledSurface:
    def test_compile_count_fixed_shape(self):
        """ACCEPTANCE: a join/leave trace over mixed prompt lengths and
        budgets compiles EXACTLY one (prefill-into-slot, decode) pair —
        slot index, prompt length and per-row depths are all traced."""
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=12,
                          adapter_cache=cache)
        rng = np.random.default_rng(6)
        for i in range(5):
            eng.submit(rng.integers(0, mcfg.vocab_size, 3 + i,
                                    dtype=np.int32),
                       adapter="t0", max_new_tokens=1 + (i % 3))
        eng.run()
        counts = eng.compile_counts()
        assert counts["prefill_into_slot"] == 1, counts
        assert counts["decode"] == {None: 1}, counts

    def test_multi_adapter_group_signatures_compile_once_each(self):
        """Mixed-handle slot tables compile one decode per grouping
        signature; re-serving the same mix reuses them all."""
        mcfg, scfg, params, cache = _setup(tenants=2)
        eng = DecodeEngine(mcfg, scfg, params, slots=4, max_len=12,
                          adapter_cache=cache)
        rng = np.random.default_rng(7)

        def serve_mix():
            for t in (0, 0, 1, 1):
                eng.submit(rng.integers(0, mcfg.vocab_size, 5,
                                        dtype=np.int32),
                           adapter=f"t{t}", max_new_tokens=4)
            return eng.run()

        serve_mix()
        counts1 = eng.compile_counts()
        serve_mix()
        assert eng.compile_counts() == counts1
        assert all(n == 1 for n in counts1["decode"].values()), counts1
        assert ((0, 2), (2, 2)) in counts1["decode"]

    def test_engine_decode_jaxpr_has_zero_norm_work(self):
        """ACCEPTANCE: the engine's decode step — per-row-length cache,
        folded serving state — contains zero ``dora_wnorm`` ops."""
        mcfg, scfg, params, cache = _setup()
        state = cache.get_state(params, cache.current_handle("t0"))
        dec_cache = init_cache(mcfg, 2, 8, row_lens=True)
        decode = make_decode_step(mcfg, scfg, None, batch=2)
        jaxpr = str(jax.make_jaxpr(decode)(
            params, state, dec_cache,
            {"tokens": jnp.zeros((2, 1), jnp.int32)}))
        assert "dora_wnorm" not in jaxpr

    def test_per_row_cache_lengths(self):
        """The cache's "len" is a [slots] vector with each row at its own
        depth: after a prefill at P and d decode writes, row j stands at
        P_j + d_j — fetched ONCE here for the assertion; the scheduler
        itself never reads it back (host mirrors only)."""
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=12,
                          adapter_cache=cache)
        rng = np.random.default_rng(8)
        # g=4: 3 decode writes; g=2: 1 decode write. Both admitted at
        # step 0, so slot 1 idles (len += 1 per decode step, garbage
        # rows) after its request retires — until the cache is reused.
        eng.submit(rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32),
                   adapter="t0", max_new_tokens=4)
        eng.submit(rng.integers(0, mcfg.vocab_size, 6, dtype=np.int32),
                   adapter="t0", max_new_tokens=2)
        eng.run()
        lens = np.asarray(eng.cache["len"])
        assert lens.shape == (2,)
        # slot 0: P=4, three decode writes -> 7
        assert lens[0] == 7, lens
        # slot 1: P=6 + one live write + one idle decode tick -> >= 7
        # (idle rows keep counting; re-admission rewinds via prefill)
        assert lens[1] >= 7, lens


class TestArchContracts:
    def test_ssm_arch_rejected_naming_the_reason(self):
        """SATELLITE: Mamba/SSM admission fails LOUDLY — the state
        integrates every token and cannot rewind to a slot's true prompt
        length."""
        mcfg = get_config("falcon-mamba-7b", smoke=True)
        scfg = StepConfig(dora=DCFG)
        params, adapters, _ = build_state(mcfg, DCFG, 0)
        with pytest.raises(NotImplementedError,
                           match="integrate every processed token"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=8,
                         adapters=adapters)
        with pytest.raises(NotImplementedError, match="cannot rewind"):
            make_prefill_into_slot_step(mcfg, scfg, None, seq=8)

    def test_moe_arch_rejected(self):
        mcfg = get_config("qwen2-moe-a2.7b", smoke=True)
        scfg = StepConfig(dora=DCFG)
        params, adapters, _ = build_state(mcfg, DCFG, 0)
        with pytest.raises(NotImplementedError, match="couples batch rows"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=8,
                         adapters=adapters)

    def test_engine_requires_exactly_one_adapter_source(self):
        """Neither source is an error; BOTH is too — a handle-less active
        slot would be indistinguishable from a free one in the grouping
        and silently decode under a neighbour's tenant state."""
        mcfg, scfg, params, cache = _setup()
        with pytest.raises(ValueError, match="not both, not neither"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=8)
        state = cache.get_state(params, cache.current_handle("t0"))
        with pytest.raises(ValueError, match="not both, not neither"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=8,
                         adapters=state, adapter_cache=cache)

    def test_stale_handle_fails_at_submit_without_wedging(self):
        """A handle that is ALREADY stale at submission can NEVER
        resolve — versions only move forward — and submit is where the
        serving tree gets pinned, so it raises right there: nothing is
        queued, nothing wedges, and the engine keeps serving."""
        from repro.core import AdapterCacheMiss
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=10,
                          adapter_cache=cache)
        rng = np.random.default_rng(12)
        stale = cache.current_handle("t0")
        _, ad_new, _ = build_state(mcfg, DCFG, 99)
        cache.update("t0", ad_new)          # stale's version is now behind
        p0 = rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
        p1 = rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
        with pytest.raises(AdapterCacheMiss, match="stale adapter handle"):
            eng.submit(p0, adapter=stale, max_new_tokens=2)
        assert not eng.has_work()            # the failed submit queued nothing
        eng.submit(p1, adapter="t0", max_new_tokens=2)   # current version
        (r1,) = eng.run()
        assert r1.finish_reason == "length" and r1.tokens.shape == (2,)
        assert not eng.has_work() and eng.stats().admitted == 1

    def test_update_mid_request_keeps_the_submitted_version_pinned(self):
        """ACCEPTANCE: the serving tree is pinned at SUBMIT. An
        AdapterStateCache.update() landing while requests are in flight
        — one decoding in its slot, one still QUEUED behind it — must
        neither error them nor re-route them: both stream the tokens of
        the version they were submitted against, and only the NEXT
        submission picks up the bumped version."""
        mcfg, scfg, params, cache = _setup()
        _, ad, _ = build_state(mcfg, DCFG, 50)
        # Seed-registered adapters have B == 0 (identity); install two
        # genuinely different non-identity versions so re-routing a
        # pinned request would actually change its stream.
        old_h = cache.update("t0", _perturb(ad, 1))
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
                   for _ in range(3)]
        # v-old oracles, computed while that version is still current
        want_old = [_alone(mcfg, scfg, params, cache, p, 3, 12, "t0")
                    for p in prompts[:2]]
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=12,
                          adapter_cache=cache)
        eng.submit(prompts[0], adapter="t0", max_new_tokens=3)
        eng.submit(prompts[1], adapter="t0", max_new_tokens=3)
        eng.step()                  # admits r0 only; r1 waits in the FIFO
        new_h = cache.update("t0", _perturb(ad, 2))     # mid-request bump
        assert new_h.version == old_h.version + 1
        eng.submit(prompts[2], adapter="t0", max_new_tokens=3)
        want_new = _alone(mcfg, scfg, params, cache, prompts[2], 3, 12,
                          "t0")
        r0, r1, r2 = eng.run()
        assert [r.finish_reason for r in (r0, r1, r2)] == ["length"] * 3
        # the running AND the queued pre-update requests kept v-old ...
        np.testing.assert_array_equal(r0.tokens, want_old[0])
        np.testing.assert_array_equal(r1.tokens, want_old[1])
        # ... and the post-update submission serves v-new
        np.testing.assert_array_equal(r2.tokens, want_new)
        assert (want_old[1].tolist() != want_new.tolist()
                or want_old[0].tolist() != want_new.tolist()), \
            "perturbed versions produced identical streams; the pinning " \
            "assertion above is vacuous — pick different perturbations"

    def test_run_delivers_results_exactly_once(self):
        """The engine persists across run() calls (EngineServer /
        MultiTenantServer reuse it): results are handed over once, not
        retained forever."""
        mcfg, scfg, params, cache = _setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=8,
                          adapter_cache=cache)
        rng = np.random.default_rng(13)
        eng.submit(rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32),
                   adapter="t0", max_new_tokens=2)
        first = eng.run()
        assert len(first) == 1
        assert eng.results() == [] and eng.run() == []
        eng.submit(rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32),
                   adapter="t0", max_new_tokens=2)
        second = eng.run()
        assert [r.request_id for r in second] == [1]

    def test_cache_mesh_fingerprint_mismatch_rejected(self):
        from repro.launch.mesh import make_debug_mesh
        mcfg, scfg, params, cache = _setup()     # cache keyed mesh=None
        mesh = make_debug_mesh(1, 1)
        with pytest.raises(ValueError, match="keyed for sharding"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=8,
                         adapter_cache=cache, mesh=mesh)


class TestEngineServer:
    def test_mixed_lengths_and_adapters_match_oracle(self):
        """EngineServer.run: mixed prompt lengths AND mixed adapters in
        one slot table; every request matches its generate() oracle."""
        mcfg, scfg, params, cache = _setup(tenants=2)
        server = EngineServer(mcfg, scfg, params, cache=cache, slots=3,
                              max_len=14)
        rng = np.random.default_rng(9)
        reqs, meta = [], []
        for i, (P, t) in enumerate([(5, 0), (7, 1), (4, 0), (6, 1)]):
            p = rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
            reqs.append(Request(p, f"t{t}"))
            meta.append((p, f"t{t}"))
        results = server.run(reqs, gen_len=4)
        for r, (p, t) in zip(results, meta):
            np.testing.assert_array_equal(
                r.tokens, _alone(mcfg, scfg, params, cache, p, 4, 14, t),
                err_msg=f"request {r.request_id} ({t})")
        assert server.engine.stats().mean_occupancy > 0.5

    def test_multitenant_server_routes_mixed_lengths_through_engine(self):
        """SATELLITE: MultiTenantServer.serve admits mixed-length batches
        via the engine (list of ragged rows, each matching its oracle);
        static=True keeps the legacy length-bucket error."""
        mcfg, scfg, params, cache = _setup(tenants=2)
        server = MultiTenantServer(mcfg, scfg, params, cache=cache)
        rng = np.random.default_rng(10)
        reqs = [Request(rng.integers(0, mcfg.vocab_size, P,
                                     dtype=np.int32), f"t{t}")
                for P, t in [(5, 0), (7, 1), (6, 0)]]
        out = server.serve(reqs, gen_len=3, max_len=12)
        assert isinstance(out, list)
        for row, r in zip(out, reqs):
            p = np.asarray(r.prompt)
            np.testing.assert_array_equal(row[:len(p)], p)
            np.testing.assert_array_equal(
                row[len(p):],
                _alone(mcfg, scfg, params, cache, p, 3, 12, r.adapter))
        with pytest.raises(ValueError, match="length bucket"):
            server.serve(reqs, gen_len=3, max_len=12, static=True)
        with pytest.raises(ValueError, match="return_logits"):
            server.serve(reqs, gen_len=3, max_len=12, return_logits=True)

    def test_failed_serve_does_not_poison_the_cached_engine(self):
        """A serve() that raises on a stale handle must leave the CACHED
        engine servable: the next call with only valid adapters works
        (regression: the stale request used to stay queued forever)."""
        from repro.core import AdapterCacheMiss
        mcfg, scfg, params, cache = _setup(tenants=2)
        server = MultiTenantServer(mcfg, scfg, params, cache=cache)
        rng = np.random.default_rng(14)
        stale = cache.current_handle("t0")
        _, ad_new, _ = build_state(mcfg, DCFG, 98)
        cache.update("t0", ad_new)
        bad = [Request(rng.integers(0, mcfg.vocab_size, 5,
                                    dtype=np.int32), stale),
               Request(rng.integers(0, mcfg.vocab_size, 6,
                                    dtype=np.int32), "t1")]
        with pytest.raises(AdapterCacheMiss, match="stale"):
            server.serve(bad, gen_len=2, max_len=10)
        good = [Request(rng.integers(0, mcfg.vocab_size, 5,
                                     dtype=np.int32), "t0"),
                Request(rng.integers(0, mcfg.vocab_size, 6,
                                     dtype=np.int32), "t1")]
        out = server.serve(good, gen_len=2, max_len=10)
        assert [len(o) for o in out] == [7, 8]

    def test_bad_request_mid_batch_queues_nothing(self):
        """All-or-nothing submission: a request that fails validation in
        the MIDDLE of a batch (unregistered adapter id / oversized
        prompt) fails the whole call before anything is queued — no
        orphans stealing slots from (or streaming into) the next call."""
        mcfg, scfg, params, cache = _setup()
        server = EngineServer(mcfg, scfg, params, cache=cache, slots=2,
                              max_len=10)
        rng = np.random.default_rng(16)
        ok = Request(rng.integers(0, mcfg.vocab_size, 5,
                                  dtype=np.int32), "t0")
        with pytest.raises(KeyError, match="not registered"):
            server.run([ok, Request(ok.prompt, "typo-id")], gen_len=2)
        with pytest.raises(ValueError, match="P \\+ 1 <= max_len"):
            server.run([ok, Request(np.zeros(10, np.int32), "t0")],
                       gen_len=2)
        assert not server.engine.has_work()
        seen = []
        results = server.run([ok], gen_len=2,
                             on_token=lambda r, t: seen.append(r))
        assert len(results) == 1 and results[0].tokens.shape == (2,)
        # only the surviving call's request ever streamed
        assert set(seen) == {results[0].request_id}

    def test_mixed_length_temperature_reproducible_across_calls(self):
        """Sampling keys fold in the request's index within the CALL, so
        repeated serves through the persistent cached engine reproduce
        their tokens (the engine's global request ids keep growing)."""
        mcfg, scfg, params, cache = _setup()
        server = MultiTenantServer(mcfg, scfg, params, cache=cache)
        rng = np.random.default_rng(15)
        reqs = [Request(rng.integers(0, mcfg.vocab_size, P,
                                     dtype=np.int32), "t0")
                for P in (5, 7)]
        out1 = server.serve(reqs, gen_len=3, max_len=12, temperature=0.9,
                            seed=5)
        out2 = server.serve(reqs, gen_len=3, max_len=12, temperature=0.9,
                            seed=5)
        for a, b in zip(out1, out2):
            np.testing.assert_array_equal(a, b)

    def test_uniform_lengths_forced_through_engine_match_static(self):
        """static=False on a uniform-length batch: engine tokens equal
        the static path's tokens (same greedy math, different scheduler)."""
        mcfg, scfg, params, cache = _setup()
        server = MultiTenantServer(mcfg, scfg, params, cache=cache)
        rng = np.random.default_rng(11)
        reqs = [Request(rng.integers(0, mcfg.vocab_size, 6,
                                     dtype=np.int32), "t0")
                for _ in range(3)]
        static = np.asarray(server.serve(reqs, gen_len=3, max_len=10))
        cont = server.serve(reqs, gen_len=3, max_len=10, static=False)
        for row, srow in zip(cont, static):
            np.testing.assert_array_equal(row, srow)


# The committed join/leave arrival trace — (arrival_step, P, gen_len)
# literals of make_arrival_trace(n_requests=12, mean_interarrival=2.0,
# prompt_len=8, gen_lens=(4, 6, 8, 10), seed=0), i.e. exactly the trace
# the BENCH_serve.json "speculative" section is gated on.
_TRACE = [(1, 8, 8), (1, 8, 6), (1, 8, 4), (4, 8, 10), (6, 8, 10),
          (11, 8, 8), (23, 8, 6), (23, 8, 10), (28, 8, 8), (30, 8, 4),
          (32, 8, 4), (32, 8, 10)]


def _drive_trace(eng, prompts, adapters):
    """Feed _TRACE into a persistent engine tick-by-tick; returns the
    {request_id: [token, ...]} STREAMS exactly as on_token emitted them
    (order within a request matters: speculative verify must release
    accepted tokens in sequence, not just end with the right array)."""
    streams: dict[int, list[int]] = {}

    def on_token(rid, tok):
        streams.setdefault(rid, []).append(tok)

    i, step = 0, 0
    while i < len(_TRACE) or eng.has_work():
        while i < len(_TRACE) and _TRACE[i][0] <= step:
            eng.submit(prompts[i], adapter=adapters[i],
                       max_new_tokens=_TRACE[i][2], key_id=i)
            i += 1
        eng.step(on_token)
        step += 1
    return streams


class TestSpeculative:
    """Speculative decode: adapter-free drafts + one batched full-DoRA
    verify per tick, rewinding each row's cache to the accepted frontier.
    The greedy contract is BITWISE: speculative streams equal plain
    decode streams token-for-token, whatever the accept rate."""
    ML = 18
    K = 3

    def _spec_setup(self, tenants=1):
        mcfg, scfg, params, cache = _setup(tenants=tenants)
        # Seed-built adapters have B == 0: the base-path draft would then
        # BE the full path and every draft would be accepted trivially.
        # Perturbed non-identity adapters make verify actually reject.
        for t in range(tenants):
            _, ad, _ = build_state(mcfg, DCFG, 10 + t)
            cache.update(f"t{t}", _perturb(ad, 7 + t))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
                   for _, P, _ in _TRACE]
        return mcfg, scfg, params, cache, prompts

    def test_speculative_streams_equal_plain_bitwise(self):
        """ACCEPTANCE: over the committed arrival trace, a speculative
        engine (k=3) streams exactly the tokens the plain engine does,
        per request, in order — while actually speculating (verify ticks
        ran, drafts were both accepted and rejected)."""
        mcfg, scfg, params, cache, prompts = self._spec_setup()
        ads = ["t0"] * len(_TRACE)
        spec = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                            adapter_cache=cache, speculative_k=self.K)
        plain = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                             adapter_cache=cache)
        got = _drive_trace(spec, prompts, ads)
        want = _drive_trace(plain, prompts, ads)
        assert got == want
        st = spec.stats()
        ps = plain.stats()
        assert st.generated_tokens == ps.generated_tokens
        # it really speculated: k drafts per verify tick, and the
        # full-DoRA step count (verify + fallback decode) needs at most
        # plain decode's steps and FEWER than the tokens plain emits —
        # the artifact gate's win condition (scripts/check_bench_drift)
        assert st.verify_steps > 0
        assert st.draft_steps == self.K * st.verify_steps
        assert st.verify_steps + st.decode_steps <= ps.decode_steps
        assert st.verify_steps + st.decode_steps < ps.generated_tokens
        # non-identity adapters make some drafts wrong: the oracle above
        # must hold THROUGH rejections, not because everything matched
        assert 0 < st.accepted_drafts < st.draft_steps, st

    def test_speculative_temperature_falls_back_to_plain(self):
        """temperature > 0 silently disables speculation (the drafts
        would bias the sample stream): the engine runs plain decode and
        the speculative counters stay zero."""
        mcfg, scfg, params, cache, prompts = self._spec_setup()
        eng = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                           adapter_cache=cache, speculative_k=self.K,
                           temperature=0.7, seed=5)
        got = _drive_trace(eng, prompts, ["t0"] * len(_TRACE))
        st = eng.stats()
        assert st.verify_steps == 0 and st.draft_steps == 0
        assert st.decode_steps > 0
        assert sum(len(v) for v in got.values()) == st.generated_tokens

    def test_speculative_compile_surface(self):
        """ACCEPTANCE: one compiled (draft, verify) pair per (slots,
        max_len, k, group-signature) — the whole committed trace, twice,
        compiles exactly 1 draft and 1 verify per signature/window, on
        top of the usual single prefill + per-signature decode."""
        mcfg, scfg, params, cache, prompts = self._spec_setup()
        ads = ["t0"] * len(_TRACE)
        eng = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                           adapter_cache=cache, speculative_k=self.K)
        _drive_trace(eng, prompts, ads)
        counts = eng.compile_counts()
        assert counts["prefill_into_slot"] == 1, counts
        assert counts["draft"] == 1, counts
        assert counts["verify"] == {(None, self.K + 1): 1}, counts
        assert all(n == 1 for n in counts["decode"].values()), counts
        # the same trace again must reuse every executable
        _drive_trace(eng, prompts, ads)
        assert eng.compile_counts() == counts

    def test_speculative_compile_surface_multi_tenant(self):
        """Mixed-handle slot tables: the verify LRU keys on (grouping
        signature, window) and compiles each exactly once."""
        mcfg, scfg, params, cache, prompts = self._spec_setup(tenants=2)
        ads = [f"t{i % 2}" for i in range(len(_TRACE))]
        eng = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                           adapter_cache=cache, speculative_k=self.K)
        got = _drive_trace(eng, prompts, ads)
        plain = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                             adapter_cache=cache)
        assert got == _drive_trace(plain, prompts, ads)
        counts = eng.compile_counts()
        assert counts["draft"] == 1, counts
        assert counts["verify"], counts
        assert all(n == 1 for n in counts["verify"].values()), counts
        assert all(window == self.K + 1
                   for _, window in counts["verify"]), counts

    def test_draft_jaxpr_has_zero_adapter_work(self):
        """ACCEPTANCE: the draft step is the BASE model — zero
        ``dora_wnorm`` ops and zero adapter matmuls (it does not even
        take an adapter argument); the verify step keeps the folded
        zero-norm property of the decode step."""
        mcfg, scfg, params, cache = _setup()
        state = cache.get_state(params, cache.current_handle("t0"))
        dec_cache = init_cache(mcfg, 2, 8, row_lens=True)
        draft = make_draft_step(mcfg, scfg, None, batch=2)
        jd = str(jax.make_jaxpr(draft)(
            params, dec_cache, {"tokens": jnp.zeros((2, 1), jnp.int32)}))
        verify = make_verify_step(mcfg, scfg, None, batch=2, window=4)
        jv = str(jax.make_jaxpr(verify)(
            params, state, dec_cache,
            {"tokens": jnp.zeros((2, 4), jnp.int32)}))
        decode = make_decode_step(mcfg, scfg, None, batch=2)
        jdec = str(jax.make_jaxpr(decode)(
            params, state, dec_cache,
            {"tokens": jnp.zeros((2, 1), jnp.int32)}))
        assert "dora_wnorm" not in jd
        assert "dora_wnorm" not in jv
        # the decode/verify steps carry the adapter (A / folded-gsB)
        # matmuls on top of the base projections; the draft must not
        assert jd.count("dot_general") < jdec.count("dot_general")
        assert jv.count("dot_general") == jdec.count("dot_general")


class TestPaged:
    """Block-paged KV cache + chunked prefill: paging is a LAYOUT
    change, not a semantics change. Greedy streams are BITWISE the
    rectangular engine's whatever the chunking, and with a chunk
    covering the whole prompt the tick-level schedule is identical too
    — while the cache lives in a block pool that drains to empty."""
    ML = 18
    BS = 6              # divides ML; default prefill_chunk = BS < P = 8

    def test_paged_streams_equal_rectangular_bitwise(self):
        """ACCEPTANCE: over the committed arrival trace, the paged
        engine (chunk = 6 < P = 8, so every admission actually streams
        in two chunks) emits exactly the rectangular engine's greedy
        streams, drains its pool, and compiles one chunk-prefill + one
        decode — never the monolithic prefill-into-slot."""
        mcfg, scfg, params, cache = _setup()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
                   for _, P, _ in _TRACE]
        ads = ["t0"] * len(_TRACE)
        rect = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                            adapter_cache=cache)
        paged = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                             adapter_cache=cache, paged=True,
                             block_size=self.BS)
        want = _drive_trace(rect, prompts, ads)
        got = _drive_trace(paged, prompts, ads)
        assert got == want
        assert paged.stats().generated_tokens == rect.stats().generated_tokens
        ps = paged.pool_stats()
        assert ps["used_blocks"] == 0, f"leaked blocks: {ps}"
        assert ps["per_slot_blocks"] == [0] * 4, ps
        assert ps["peak_used_blocks"] > 0, ps
        counts = paged.compile_counts()
        assert counts["prefill_into_slot"] == 0, counts
        assert counts["prefill_chunk"] == 1, counts
        assert counts["decode"] == {None: 1}, counts

    def test_chunk_covering_prompt_reproduces_rect_schedule(self):
        """With prefill_chunk >= P every admission completes in ONE
        tick, so the paged engine's tick-level counters — not just its
        streams — equal the rectangular engine's exactly."""
        mcfg, scfg, params, cache = _setup()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
                   for _, P, _ in _TRACE]
        ads = ["t0"] * len(_TRACE)
        rect = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                            adapter_cache=cache)
        paged = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                             adapter_cache=cache, paged=True,
                             block_size=self.BS, prefill_chunk=9)
        want = _drive_trace(rect, prompts, ads)
        got = _drive_trace(paged, prompts, ads)
        assert got == want
        st_r, st_p = rect.stats(), paged.stats()
        for field in ("steps", "decode_steps", "prefills",
                      "generated_tokens", "slot_steps"):
            assert getattr(st_p, field) == getattr(st_r, field), field

    def test_paged_speculative_streams_bitwise(self):
        """Speculation composes with paging: a speculative paged engine
        (non-identity adapters, so drafts are genuinely rejected AND
        accepted) streams exactly the plain RECTANGULAR engine's greedy
        tokens, and the rewind's block release leaves the pool drained."""
        mcfg, scfg, params, cache = _setup()
        _, ad, _ = build_state(mcfg, DCFG, 10)
        cache.update("t0", _perturb(ad, 7))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
                   for _, P, _ in _TRACE]
        ads = ["t0"] * len(_TRACE)
        spec = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                            adapter_cache=cache, paged=True,
                            block_size=self.BS, speculative_k=3)
        plain = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                             adapter_cache=cache)
        got = _drive_trace(spec, prompts, ads)
        want = _drive_trace(plain, prompts, ads)
        assert got == want
        st = spec.stats()
        assert st.verify_steps > 0
        assert 0 < st.accepted_drafts < st.draft_steps, st
        assert spec.pool_stats()["used_blocks"] == 0

    def test_small_pool_reclaims_and_stays_bitwise(self):
        """A pool SMALLER than slots * max_blocks forces head-of-line
        deferral and reclaim preemption mid-trace — the streams must
        still be bitwise the rectangular engine's, and the pool must
        never exceed its capacity nor leak."""
        mcfg, scfg, params, cache = _setup()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
                   for _, P, _ in _TRACE]
        ads = ["t0"] * len(_TRACE)
        rect = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                            adapter_cache=cache)
        small = DecodeEngine(mcfg, scfg, params, slots=4, max_len=self.ML,
                             adapter_cache=cache, paged=True,
                             block_size=self.BS, n_blocks=8)  # < 4*3
        want = _drive_trace(rect, prompts, ads)
        got = _drive_trace(small, prompts, ads)
        assert got == want
        ps = small.pool_stats()
        assert ps["used_blocks"] == 0, ps
        assert 0 < ps["peak_used_blocks"] <= 8, ps

    def test_paged_constructor_contracts(self):
        """Paged kwargs on a rectangular engine, a non-dividing block
        size, and an undersized pool are rejected loudly."""
        mcfg, scfg, params, cache = _setup()
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=self.ML,
                         adapter_cache=cache, block_size=self.BS)
        with pytest.raises(ValueError, match="multiple"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=self.ML,
                         adapter_cache=cache, paged=True, block_size=5)
        with pytest.raises(ValueError, match="n_blocks"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=self.ML,
                         adapter_cache=cache, paged=True,
                         block_size=self.BS, n_blocks=2)


class TestFleetServing:
    """PR 9 traced dynamic grouping + the per-adapter rate limit.

    Contract under test: a DYNAMIC engine (``dynamic_grouping=True``)
    serves arbitrary tenant mixes through exactly ONE decode executable
    (tenant churn changes values — stack rows, the per-row index — never
    the compile signature) while streaming tokens bitwise-identical to
    the STATIC-signature engine and to each request served alone."""
    ML = 14

    def _fleet(self, tenants=3, dtype=None):
        mcfg, scfg, params, cache = _setup(tenants=tenants, dtype=dtype)
        # distinct non-zero B per tenant: seed-built trees have B == 0,
        # so every tenant would otherwise stream identical tokens and a
        # mis-indexed fleet stack could never be caught.
        for t in range(tenants):
            cache.update(f"t{t}", _perturb(cache.adapters(f"t{t}"), 40 + t))
        return mcfg, scfg, params, cache

    def _trace(self, mcfg, n=7, tenants=3, seed=0):
        rng = np.random.default_rng(seed)
        return [(rng.integers(0, mcfg.vocab_size, 4 + (i % 3),
                              dtype=np.int32),
                 3 + (i % 3), f"t{i % tenants}") for i in range(n)]

    def _run(self, mcfg, scfg, params, cache, reqs, **kw):
        eng = DecodeEngine(mcfg, scfg, params, slots=3, max_len=self.ML,
                           adapter_cache=cache, **kw)
        for p, g, a in reqs:
            eng.submit(p, adapter=a, max_new_tokens=g)
        res = eng.run()
        return eng, {r.request_id: r.tokens.tolist() for r in res}

    def test_dynamic_streams_match_static_and_oracle_bitwise(self):
        """ACCEPTANCE: a mixed-tenant trace through the dynamic engine is
        bitwise the static-signature engine AND each request served alone
        (per-tenant sequential serving)."""
        mcfg, scfg, params, cache = self._fleet()
        reqs = self._trace(mcfg)
        e_dyn, dyn = self._run(mcfg, scfg, params, cache, reqs,
                               dynamic_grouping=True)
        _, sta = self._run(mcfg, scfg, params, cache, reqs)
        assert dyn == sta, "dynamic streams diverged from static grouping"
        for (p, g, a), (rid, toks) in zip(reqs, sorted(dyn.items())):
            np.testing.assert_array_equal(
                toks, _alone(mcfg, scfg, params, cache, p, g, self.ML, a),
                err_msg=f"request {rid} under dynamic grouping diverged "
                        f"from serving it alone")
        counts = e_dyn.compile_counts()
        assert counts["decode"] == {"dynamic": 1}, counts
        assert counts["adapter_insert"] == 1, counts

    def test_compile_counts_are_churn_invariant(self):
        """ACCEPTANCE (seeded mirror of the hypothesis churn fuzzer): N
        adapters ≫ slots, random submit/update interleavings across
        waves — the dynamic engine ends every wave with exactly ONE
        decode executable and ONE adapter_insert executable, and every
        request finishes exactly once."""
        tenants = 4
        mcfg, scfg, params, cache = self._fleet(tenants=tenants)
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=self.ML,
                           adapter_cache=cache, dynamic_grouping=True)
        rng = np.random.default_rng(7)
        submitted, finished = [], []
        for wave in range(3):
            for _ in range(4):
                t = int(rng.integers(tenants))
                p = rng.integers(0, mcfg.vocab_size,
                                 int(rng.integers(3, 7)), dtype=np.int32)
                submitted.append(eng.submit(
                    p, adapter=f"t{t}", max_new_tokens=int(
                        rng.integers(2, 5))))
            for _ in range(int(rng.integers(1, 6))):
                if eng.has_work():
                    eng.step()
            finished += [r.request_id for r in eng.pop_results()]
            # churn BETWEEN waves: version-bump a random tenant (new
            # handle → new stack position; pinned in-flight states keep
            # serving v_old) and drop another tenant's cached state.
            bump = int(rng.integers(tenants))
            cache.update(f"t{bump}",
                         _perturb(cache.adapters(f"t{bump}"), 90 + wave))
            cache.invalidate(f"t{int(rng.integers(tenants))}")
            counts = eng.compile_counts()
            assert counts["decode"] == {"dynamic": 1}, (wave, counts)
        finished += [r.request_id for r in eng.run()]
        assert sorted(finished) == sorted(submitted), \
            "requests lost or double-finished under churn"
        assert len(set(finished)) == len(finished)
        counts = eng.compile_counts()
        assert counts["decode"] == {"dynamic": 1}, counts
        assert counts["adapter_insert"] == 1, counts
        assert counts["prefill_into_slot"] == 1, counts
        assert eng.stats().stack_inserts > 0
        # fleet positions drained with the slot table
        assert len(eng._dyn_free) == eng.slots and not eng._dyn_pos

    def test_dynamic_speculative_and_paged_stay_bitwise(self):
        """The dynamic stack composes with the PR-8 tick modes: greedy
        speculative and paged dynamic streams equal the plain static
        streams bitwise, with one ("dynamic", window) verify signature."""
        mcfg, scfg, params, cache = self._fleet()
        reqs = self._trace(mcfg)
        _, plain = self._run(mcfg, scfg, params, cache, reqs)
        e_spec, spec = self._run(mcfg, scfg, params, cache, reqs,
                                 dynamic_grouping=True, speculative_k=2)
        assert spec == plain
        assert list(e_spec.compile_counts()["verify"]) == [("dynamic", 3)]
        e_paged, paged = self._run(mcfg, scfg, params, cache, reqs,
                                   dynamic_grouping=True, paged=True)
        assert paged == plain
        assert e_paged.pool_stats()["used_blocks"] == 0

    def test_bf16_fleet_stack_holds_bf16_gsb(self):
        """A bf16 dynamic engine's fleet stack keeps every lane's folded
        gsB in bf16 (no fp32 copy for the decode step to re-round per
        token), beside fp32 g."""
        mcfg, scfg, params, cache = self._fleet(dtype=jnp.bfloat16)
        eng, _ = self._run(mcfg, scfg, params, cache, self._trace(mcfg),
                           dynamic_grouping=True)
        folded = [n for n in jax.tree.leaves(
            eng._dyn_stack,
            is_leaf=lambda n: isinstance(n, dict) and "gsB" in n)
            if isinstance(n, dict)]
        assert folded
        for leaf in folded:
            assert leaf["gsB"].dtype == jnp.bfloat16
            assert leaf["gsB"].shape[1] == eng.slots
            assert leaf["g"].dtype == jnp.float32

    def test_bf16_dynamic_streams_match_alone_bitwise(self):
        """The dynamic-vs-sequential contract at bf16: a mixed-tenant
        trace through the fleet stack streams bitwise what each request
        streams served alone with its tenant's folded state."""
        mcfg, scfg, params, cache = self._fleet(dtype=jnp.bfloat16)
        reqs = self._trace(mcfg)
        _, dyn = self._run(mcfg, scfg, params, cache, reqs,
                           dynamic_grouping=True)
        for (p, g, a), (rid, toks) in zip(reqs, sorted(dyn.items())):
            np.testing.assert_array_equal(
                toks, _alone(mcfg, scfg, params, cache, p, g, self.ML, a),
                err_msg=f"request {rid} under bf16 dynamic grouping "
                        f"diverged from serving it alone")

    def test_bf16_fleet_decode_logits_match_single_tenant_bitwise(self):
        """One fleet decode step over the K-lane stack of bf16-folded
        states: each row's logits are bitwise the single-tenant folded
        decode of that row under its own tenant's state."""
        from repro.core import stack_adapter_states
        mcfg, scfg, params, cache = self._fleet(dtype=jnp.bfloat16)
        states = [cache.get_state(params, cache.current_handle(f"t{t}"))
                  for t in range(3)]
        rows = 3
        cache_tree = init_cache(mcfg, rows, 8, row_lens=True)
        toks = jnp.asarray(np.random.default_rng(5).integers(
            0, mcfg.vocab_size, (rows, 1)), jnp.int32)
        idx = np.array([2, 0, 1], np.int32)
        fleet, _ = jax.jit(make_decode_step(
            mcfg, scfg, batch=rows, dynamic_groups=True))(
            params, stack_adapter_states(states, axis=1), cache_tree,
            {"tokens": toks, "adapter_idx": jnp.asarray(idx)})
        single = jax.jit(make_decode_step(mcfg, scfg, batch=rows))
        for b, k in enumerate(idx):
            want, _ = single(params, states[k], cache_tree, {"tokens": toks})
            np.testing.assert_array_equal(
                np.asarray(fleet[b]), np.asarray(want[b]),
                err_msg=f"row {b} (tenant {k}) of the bf16 fleet decode")

    def test_max_active_per_adapter_prevents_starvation(self):
        """SATELLITE: a hot tenant's burst is rate-limited to its slot
        share — the fleet's other tenants admit and finish while the
        burst drains, instead of queueing behind it."""
        mcfg, scfg, params, cache = self._fleet(tenants=2)
        eng = DecodeEngine(mcfg, scfg, params, slots=3, max_len=self.ML,
                           adapter_cache=cache, max_active_per_adapter=1)
        rng = np.random.default_rng(3)
        p = rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
        hot = [eng.submit(p, adapter="t0", max_new_tokens=4)
               for _ in range(5)]
        other = eng.submit(p, adapter="t1", max_new_tokens=4)
        max_hot = 0
        while eng.has_work():
            eng.step()
            max_hot = max(max_hot, sum(
                1 for s in eng._slots
                if s.occupied and s.handle.adapter_id == "t0"))
        results = {r.request_id: r for r in eng.pop_results()}
        assert max_hot == 1, \
            f"rate limit violated: {max_hot} concurrent t0 slots"
        assert len(results) == 6
        assert all(r.finish_reason == "length" for r in results.values())
        # no starvation: t1 finished before the hot burst drained
        assert results[other].finished_step < max(
            results[rid].finished_step for rid in hot)
        # the limit never displaced anyone — it holds requests in the
        # queue, it does not preempt
        assert eng.stats().preemptions == 0

    def test_rate_limited_requests_keep_queue_order(self):
        """Held-back requests keep their queue positions: once the hot
        tenant's slot frees, its NEXT request admits in FIFO order."""
        mcfg, scfg, params, cache = self._fleet(tenants=2)
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=self.ML,
                           adapter_cache=cache, max_active_per_adapter=1)
        rng = np.random.default_rng(4)
        p = rng.integers(0, mcfg.vocab_size, 4, dtype=np.int32)
        rids = [eng.submit(p, adapter="t0", max_new_tokens=3)
                for _ in range(3)]
        results = {r.request_id: r for r in eng.run()}
        admits = [results[r].admitted_step for r in rids]
        assert admits == sorted(admits), "rate-limited FIFO order broken"

    def test_dynamic_requires_adapter_cache(self):
        mcfg, scfg, params, cache = _setup()
        h = cache.current_handle("t0")
        fixed = cache.get_state(params, h)
        with pytest.raises(ValueError, match="dynamic_grouping"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=10,
                         adapters=fixed, dynamic_grouping=True)
        with pytest.raises(ValueError, match="max_active_per_adapter"):
            DecodeEngine(mcfg, scfg, params, slots=2, max_len=10,
                         adapter_cache=cache, max_active_per_adapter=0)

    def test_dynamic_decode_jaxpr_has_zero_norm_work(self):
        """The dynamic grouped step keeps the serving contract: zero
        ``dora_wnorm`` ops per token (all norm work was precomputed)."""
        mcfg, scfg, params, cache = self._fleet()
        eng, _ = self._run(mcfg, scfg, params, cache, self._trace(mcfg),
                           dynamic_grouping=True)
        step = make_decode_step(mcfg, scfg, batch=3, dynamic_groups=True)
        groups, adapters = eng._slot_grouping()
        assert groups == "dynamic"
        cache_tree = init_cache(mcfg, 3, self.ML, row_lens=True)
        jaxpr = jax.make_jaxpr(step)(
            params, adapters, cache_tree,
            {"tokens": jnp.zeros((3, 1), jnp.int32),
             "adapter_idx": jnp.zeros((3,), jnp.int32)})
        assert "dora_wnorm" not in str(jaxpr), \
            "dynamic decode recomputes norm work per token"


# ---------------------------------------------------------------------------
# Forced 2-device mesh (subprocess): join/leave trace under SPMD.
# ---------------------------------------------------------------------------

def _run_subprocess(code: str, devices: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    env.pop("REPRO_FORCE_TIER", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


_ENGINE_SPMD = """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import AdapterStateCache, DoRAConfig
    from repro.launch.engine import DecodeEngine
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import generate
    from repro.launch.steps import StepConfig
    from repro.launch.train import build_state

    assert jax.device_count() == 2
    mesh = make_debug_mesh(2, 1)     # slots shard over the data axis
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
    mcfg = get_config("qwen2-7b", smoke=True)
    scfg = StepConfig(dora=DCFG)
    params, _, _ = build_state(mcfg, DCFG, 0)
    cache = AdapterStateCache.for_serving(mcfg, scfg, mesh)
    _, ad, _ = build_state(mcfg, DCFG, 10)
    cache.register("t0", ad)

    ML = 12
    eng = DecodeEngine(mcfg, scfg, params, slots=4, max_len=ML,
                       adapter_cache=cache, mesh=mesh)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, mcfg.vocab_size, P, dtype=np.int32), g)
            for P, g in [(5, 5), (6, 2), (4, 4), (5, 3), (6, 4)]]
    for p, g in reqs:
        eng.submit(p, adapter="t0", max_new_tokens=g)
    results = eng.run()
    counts = eng.compile_counts()
    assert counts["prefill_into_slot"] == 1, counts
    assert counts["decode"] == {None: 1}, counts
    for r, (p, g) in zip(results, reqs):
        ref = np.asarray(generate(mcfg, params, cache.current_handle("t0"),
                                  scfg, p[None], gen_len=g, max_len=ML,
                                  adapter_cache=cache, mesh=mesh))
        assert np.array_equal(r.tokens, ref[0, len(p):]), r.request_id
    print("ENGINE_SPMD_OK")
"""


@pytest.mark.slow
def test_engine_spmd_join_leave():
    """Acceptance on a forced 2-device CPU mesh: a join/leave trace
    through slots sharded over the data axis serves every request the
    same greedy tokens as generate() alone under the same mesh, with one
    compiled (prefill, decode) pair."""
    out = _run_subprocess(_ENGINE_SPMD, 2)
    assert "ENGINE_SPMD_OK" in out, out


_SPEC_SPMD = """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import AdapterStateCache, DoRAConfig
    from repro.launch.engine import DecodeEngine
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.steps import StepConfig
    from repro.launch.train import build_state

    assert jax.device_count() == 2
    mesh = make_debug_mesh(2, 1)     # slots shard over the data axis
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
    mcfg = get_config("qwen2-7b", smoke=True)
    scfg = StepConfig(dora=DCFG)
    params, _, _ = build_state(mcfg, DCFG, 0)
    cache = AdapterStateCache.for_serving(mcfg, scfg, mesh)
    _, ad, _ = build_state(mcfg, DCFG, 10)
    cache.register("t0", ad)
    # non-identity adapters (random B, seed A/m): verify must actually
    # reject some drafts AND accept some — see _perturb in the test file
    key = jax.random.PRNGKey(7)
    cnt = [0]

    def perturb(path, leaf):
        cnt[0] += 1
        if "'B'" in "/".join(str(p) for p in path):
            return jax.random.normal(jax.random.fold_in(key, cnt[0]),
                                     leaf.shape, leaf.dtype) * 0.1
        return leaf
    cache.update("t0", jax.tree_util.tree_map_with_path(perturb, ad))

    # the committed arrival trace (see _TRACE in tests/test_engine.py)
    TRACE = [(1, 8, 8), (1, 8, 6), (1, 8, 4), (4, 8, 10), (6, 8, 10),
             (11, 8, 8), (23, 8, 6), (23, 8, 10), (28, 8, 8), (30, 8, 4),
             (32, 8, 4), (32, 8, 10)]
    ML, K = 18, 3
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
               for _, P, _ in TRACE]

    def drive(eng):
        streams = {}
        i, step = 0, 0
        while i < len(TRACE) or eng.has_work():
            while i < len(TRACE) and TRACE[i][0] <= step:
                eng.submit(prompts[i], adapter="t0",
                           max_new_tokens=TRACE[i][2], key_id=i)
                i += 1
            eng.step(lambda rid, tok: streams.setdefault(rid,
                                                         []).append(tok))
            step += 1
        return streams

    spec = DecodeEngine(mcfg, scfg, params, slots=4, max_len=ML,
                        adapter_cache=cache, mesh=mesh, speculative_k=K)
    plain = DecodeEngine(mcfg, scfg, params, slots=4, max_len=ML,
                         adapter_cache=cache, mesh=mesh)
    got, want = drive(spec), drive(plain)
    assert got == want, "speculative streams diverged from plain decode"
    st = spec.stats()
    assert st.verify_steps > 0 and st.draft_steps == K * st.verify_steps
    assert 0 < st.accepted_drafts < st.draft_steps, st
    counts = spec.compile_counts()
    assert counts["draft"] == 1, counts
    assert counts["verify"] == {(None, K + 1): 1}, counts
    print("SPEC_SPMD_OK")
"""


@pytest.mark.slow
def test_engine_spmd_speculative_oracle():
    """Acceptance on a forced 2-device CPU mesh: speculative decode over
    the committed arrival trace streams exactly the plain engine's greedy
    tokens, with one compiled (draft, verify) pair, while genuinely
    accepting AND rejecting drafts."""
    out = _run_subprocess(_SPEC_SPMD, 2)
    assert "SPEC_SPMD_OK" in out, out


_PAGED_SPMD = """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import AdapterStateCache, DoRAConfig
    from repro.launch.engine import DecodeEngine
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.steps import StepConfig
    from repro.launch.train import build_state

    assert jax.device_count() == 2
    mesh = make_debug_mesh(2, 1)     # slots shard over the data axis
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
    mcfg = get_config("qwen2-7b", smoke=True)
    scfg = StepConfig(dora=DCFG)
    params, _, _ = build_state(mcfg, DCFG, 0)
    cache = AdapterStateCache.for_serving(mcfg, scfg, mesh)
    _, ad, _ = build_state(mcfg, DCFG, 10)
    cache.register("t0", ad)

    ML = 12
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, mcfg.vocab_size, P, dtype=np.int32), g)
            for P, g in [(5, 5), (6, 2), (4, 4), (5, 3), (6, 4)]]

    # prefill_chunk=3 < every P: admission genuinely streams in chunks
    # under SPMD (the block pool is replicated host state; the pool
    # arrays shard like the rectangular cache did)
    paged = DecodeEngine(mcfg, scfg, params, slots=4, max_len=ML,
                         adapter_cache=cache, mesh=mesh, paged=True,
                         block_size=6, prefill_chunk=3)
    rect = DecodeEngine(mcfg, scfg, params, slots=4, max_len=ML,
                        adapter_cache=cache, mesh=mesh)
    for p, g in reqs:
        paged.submit(p, adapter="t0", max_new_tokens=g)
        rect.submit(p, adapter="t0", max_new_tokens=g)
    got = paged.run()
    want = rect.run()
    for rp, rr in zip(got, want):
        assert np.array_equal(rp.tokens, rr.tokens), rp.request_id
    counts = paged.compile_counts()
    assert counts["prefill_into_slot"] == 0, counts
    assert counts["prefill_chunk"] == 1, counts
    assert counts["decode"] == {None: 1}, counts
    ps = paged.pool_stats()
    assert ps["used_blocks"] == 0 and ps["peak_used_blocks"] > 0, ps
    print("PAGED_SPMD_OK")
"""


@pytest.mark.slow
def test_engine_spmd_paged_oracle():
    """Acceptance on a forced 2-device CPU mesh: the block-paged engine
    with multi-chunk admission streams exactly the rectangular engine's
    greedy tokens under SPMD, with one compiled chunk-prefill + decode
    pair and a fully drained pool."""
    out = _run_subprocess(_PAGED_SPMD, 2)
    assert "PAGED_SPMD_OK" in out, out
