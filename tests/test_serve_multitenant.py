"""Multi-tenant serving: the grouped gsB-folded compose, request routing
through the adapter-state LRU, and the acceptance contract — a mixed
N≥3-adapter batch decodes in ONE step, bitwise-equal (fp32) to serving
each tenant sequentially with its own precomputed state, with zero
``dora_wnorm``-tagged ops in the grouped decode jaxpr.

Multi-device parity runs in a subprocess (same pattern as
``test_compose_spmd.py``): the forced-device-count XLA flag must be set
before jax initializes.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (AdapterCacheMiss, AdapterStateCache, DoRAConfig,
                        dora_linear, dora_linear_grouped, init_dora_params,
                        precompute_adapter_state, stack_adapter_states)
from repro.launch.serve import MultiTenantServer, Request, generate
from repro.launch.steps import StepConfig, make_decode_step
from repro.launch.train import build_state

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
ARCH = "qwen2-7b"


def _tenants(W, n, *, fold_gsb=True):
    key = jax.random.PRNGKey(7)
    states, raws = [], []
    for k in range(n):
        adp = init_dora_params(jax.random.fold_in(key, k), W, DCFG)
        adp["B"] = 0.2 * jax.random.normal(jax.random.fold_in(key, 50 + k),
                                           adp["B"].shape)
        raws.append(adp)
        states.append(precompute_adapter_state(
            W, adp, DCFG, act_dtype=jnp.float32, fold_gsb=fold_gsb))
    return raws, states


class TestGroupedLinear:
    D_IN, D_OUT, K = 64, 96, 3

    def _xW(self, rows):
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, rows + (self.D_IN,), jnp.float32)
        W = jax.random.normal(jax.random.fold_in(key, 1),
                              (self.D_OUT, self.D_IN))
        return x, W

    @pytest.mark.parametrize("seq", [1, 5])
    def test_grouped_bitwise_vs_homogeneous(self, seq):
        """Each ≥2-row group through the grouped path is BITWISE the
        homogeneous gsB fast path on the same rows — decode (S=1) and
        prefill (S>1) shapes."""
        x, W = self._xW((2 * self.K, seq))
        _, states = _tenants(W, self.K)
        stacked = stack_adapter_states(states, axis=0)
        groups = tuple((2 * k, 2) for k in range(self.K))
        yg = jax.jit(lambda x: dora_linear_grouped(
            x, W, stacked, DCFG, groups))(x)
        for k in range(self.K):
            sl = slice(2 * k, 2 * k + 2)
            yh = jax.jit(lambda xs, st=states[k]: dora_linear(
                xs, W, st, DCFG, training=False))(x[sl])
            np.testing.assert_array_equal(np.asarray(yh),
                                          np.asarray(yg[sl]),
                                          err_msg=f"tenant {k} seq {seq}")

    def test_uneven_groups_and_bias(self):
        x, W = self._xW((5, 1))
        _, states = _tenants(W, 2)
        stacked = stack_adapter_states(states, axis=0)
        bias = jax.random.normal(jax.random.PRNGKey(3), (self.D_OUT,))
        groups = ((0, 3), (3, 2))
        yg = dora_linear_grouped(x, W, stacked, DCFG, groups, bias=bias)
        for k, (s, n) in enumerate(groups):
            yh = dora_linear(x[s:s + n], W, states[k], DCFG, bias=bias,
                             training=False)
            np.testing.assert_allclose(np.asarray(yh),
                                       np.asarray(yg[s:s + n]),
                                       rtol=0, atol=0)

    def test_requires_folded_state(self):
        x, W = self._xW((4, 1))
        _, states = _tenants(W, 2, fold_gsb=False)
        stacked = stack_adapter_states(states, axis=0)
        with pytest.raises(ValueError, match="gsB"):
            dora_linear_grouped(x, W, stacked, DCFG, ((0, 2), (2, 2)))

    def test_serving_only(self):
        x, W = self._xW((4, 1))
        _, states = _tenants(W, 2)
        stacked = stack_adapter_states(states, axis=0)
        with pytest.raises(ValueError, match="serving-only"):
            dora_linear(x, W, stacked, DCFG, training=True,
                        tenant_groups=((0, 2), (2, 2)))

    def test_bad_groupings_rejected(self):
        x, W = self._xW((4, 1))
        _, states = _tenants(W, 2)
        stacked = stack_adapter_states(states, axis=0)
        for groups, match in [
            (((0, 2), (3, 1)), "contiguously"),     # gap
            (((0, 2), (2, 1)), "cover"),            # short
            (((0, 4),), "tenant groups but"),       # K mismatch
            ((), "at least one"),
        ]:
            with pytest.raises(ValueError, match=match):
                dora_linear_grouped(x, W, stacked, DCFG, groups)

    def test_stacked_weights_unsupported(self):
        key = jax.random.PRNGKey(2)
        W = jax.random.normal(key, (2, 96, 64))
        _, states = _tenants(W, 2)
        stacked = stack_adapter_states(states, axis=0)
        x = jax.random.normal(key, (4, 1, 64))
        with pytest.raises(NotImplementedError, match="stacked"):
            dora_linear_grouped(x, W, stacked, DCFG, ((0, 2), (2, 2)))


class TestGroupedModel:
    def _setup(self, n=3):
        mcfg = get_config(ARCH, smoke=True)
        scfg = StepConfig(dora=DCFG)
        params, _, _ = build_state(mcfg, DCFG, 0)
        cache = AdapterStateCache.for_serving(mcfg, scfg)
        for t in range(n):
            _, ad, _ = build_state(mcfg, DCFG, 10 + t)
            cache.register(f"t{t}", ad)
        return mcfg, scfg, params, cache

    def test_grouped_decode_jaxpr_has_zero_norm_work(self):
        """Acceptance: the grouped decode step (cache hit) contains no
        ``dora_wnorm``-tagged op — a mixed-adapter batch does zero
        factored-norm work per token."""
        mcfg, scfg, params, cache = self._setup()
        states = [cache.get_state(params, cache.current_handle(f"t{t}"))
                  for t in range(3)]
        stacked = stack_adapter_states(states, axis=1)
        groups = ((0, 2), (2, 2), (4, 2))
        from repro.models import init_cache
        dec_cache = init_cache(mcfg, 6, 8)
        decode = make_decode_step(mcfg, scfg, None, batch=6,
                                  tenant_groups=groups)
        jaxpr = str(jax.make_jaxpr(decode)(
            params, stacked, dec_cache,
            {"tokens": jnp.zeros((6, 1), jnp.int32)}))
        assert "dora_wnorm" not in jaxpr

    def test_mamba_arch_rejected(self):
        mcfg = get_config("falcon-mamba-7b", smoke=True)
        scfg = StepConfig(dora=DCFG)
        params, adapters, _ = build_state(mcfg, DCFG, 0)
        from repro.models import forward, init_cache
        with pytest.raises(NotImplementedError, match="attention"):
            jax.eval_shape(
                lambda p, a: forward(
                    mcfg, p, a, DCFG, tokens=jnp.zeros((2, 1), jnp.int32),
                    cache=init_cache(mcfg, 2, 4), training=False,
                    tenant_groups=((0, 2),)),
                params, adapters)

    def test_forward_training_rejected(self):
        mcfg = get_config(ARCH, smoke=True)
        params, adapters, _ = build_state(mcfg, DCFG, 0)
        from repro.models import forward
        with pytest.raises(ValueError, match="serving-only"):
            forward(mcfg, params, adapters, DCFG,
                    tokens=jnp.zeros((2, 4), jnp.int32), training=True,
                    tenant_groups=((0, 2),))


class TestServer:
    P, G, ML = 6, 4, 12

    def _requests(self, cache, mcfg, tenants=3, rows=2, seed=0):
        rng = np.random.default_rng(seed)
        reqs = []
        for t in range(tenants):
            for _ in range(rows):
                reqs.append(Request(
                    rng.integers(0, mcfg.vocab_size, self.P,
                                 dtype=np.int32), f"t{t}"))
        # interleave tenants so the server's sort actually permutes
        order = rng.permutation(len(reqs))
        return [reqs[i] for i in order]

    def _setup(self, n=3, mesh=None):
        mcfg = get_config(ARCH, smoke=True)
        scfg = StepConfig(dora=DCFG)
        params, _, _ = build_state(mcfg, DCFG, 0)
        cache = AdapterStateCache.for_serving(mcfg, scfg, mesh)
        for t in range(n):
            _, ad, _ = build_state(mcfg, DCFG, 10 + t)
            cache.register(f"t{t}", ad)
        server = MultiTenantServer(mcfg, scfg, params, cache=cache,
                                   mesh=mesh)
        return mcfg, scfg, params, cache, server

    def test_mixed_batch_bitwise_equals_sequential(self):
        """ACCEPTANCE: N=3 adapters in one batch — logits (every sampled
        step) and tokens bitwise-equal (fp32) to serving each tenant
        sequentially with its own precomputed state."""
        mcfg, scfg, params, cache, server = self._setup()
        reqs = self._requests(cache, mcfg)
        toks, logits = server.serve(reqs, gen_len=self.G, max_len=self.ML,
                                    return_logits=True)
        toks = np.asarray(toks)
        assert len(logits) == self.G
        for t in range(3):
            rows = [i for i, r in enumerate(reqs) if r.adapter == f"t{t}"]
            prompts = np.stack([np.asarray(reqs[i].prompt) for i in rows])
            st, sl = generate(mcfg, params, cache.current_handle(f"t{t}"),
                              scfg, prompts, gen_len=self.G,
                              max_len=self.ML, adapter_cache=cache,
                              return_logits=True)
            np.testing.assert_array_equal(np.asarray(st), toks[rows],
                                          err_msg=f"tokens t{t}")
            for s in range(self.G):
                np.testing.assert_array_equal(sl[s], logits[s][rows],
                                              err_msg=f"logits t{t} "
                                                      f"step {s}")

    def test_homogeneous_batch_keeps_single_tenant_path(self):
        """All-one-adapter batches route through today's single-tenant
        loop bitwise (no grouping, no stacked tree)."""
        mcfg, scfg, params, cache, server = self._setup(n=1)
        rng = np.random.default_rng(1)
        prompts = rng.integers(0, mcfg.vocab_size, (4, self.P),
                               dtype=np.int32)
        reqs = [Request(p, "t0") for p in prompts]
        toks = np.asarray(server.serve(reqs, gen_len=self.G,
                                       max_len=self.ML))
        ref = np.asarray(generate(
            mcfg, params, cache.current_handle("t0"), scfg, prompts,
            gen_len=self.G, max_len=self.ML, adapter_cache=cache))
        np.testing.assert_array_equal(toks, ref)
        # the single-tenant path compiled with groups=None
        assert all(k[3] is None for k in server._steps)

    def test_allow_miss_false_rejects_cold_state(self):
        mcfg, scfg, params, cache, server = self._setup()
        reqs = self._requests(cache, mcfg)
        with pytest.raises(AdapterCacheMiss, match="allow_miss"):
            server.serve(reqs, gen_len=2, max_len=self.ML,
                         allow_miss=False)
        # warming every tenant makes the same call pass
        for t in range(3):
            cache.get_state(params, cache.current_handle(f"t{t}"))
        server.serve(reqs, gen_len=2, max_len=self.ML, allow_miss=False)

    def test_generate_rejects_stale_handle(self):
        """The satellite contract: a handle whose version is behind the
        registry is ALWAYS rejected with the key fields named — swapping
        adapters without re-precomputing can never serve stale logits."""
        mcfg, scfg, params, cache, _ = self._setup()
        rng = np.random.default_rng(2)
        prompts = rng.integers(0, mcfg.vocab_size, (2, self.P),
                               dtype=np.int32)
        h0 = cache.current_handle("t0")
        _, ad_new, _ = build_state(mcfg, DCFG, 42)
        cache.update("t0", ad_new)
        with pytest.raises(AdapterCacheMiss) as ei:
            generate(mcfg, params, h0, scfg, prompts, gen_len=2,
                     max_len=self.ML, adapter_cache=cache)
        msg = str(ei.value)
        assert "stale adapter handle" in msg
        for field in ("adapter_id='t0'", "version=0", "act_dtype",
                      "fold_gsb"):
            assert field in msg, (field, msg)

    def test_generate_handle_without_cache_rejected(self):
        mcfg, scfg, params, cache, _ = self._setup()
        rng = np.random.default_rng(3)
        prompts = rng.integers(0, mcfg.vocab_size, (2, self.P),
                               dtype=np.int32)
        with pytest.raises(ValueError, match="adapter_cache"):
            generate(mcfg, params, cache.current_handle("t0"), scfg,
                     prompts, gen_len=2, max_len=self.ML)

    def test_cache_mesh_fingerprint_mismatch_rejected(self):
        """A cache keyed for one mesh must not serve another: the cached
        states would be re-laid-out every step. Both the server ctor and
        handle-resolving generate() refuse loudly."""
        from repro.launch.mesh import make_debug_mesh
        mcfg, scfg, params, cache, _ = self._setup()   # cache: mesh=None
        mesh = make_debug_mesh(1, 1)
        with pytest.raises(ValueError, match="keyed for sharding"):
            MultiTenantServer(mcfg, scfg, params, cache=cache, mesh=mesh)
        rng = np.random.default_rng(4)
        prompts = rng.integers(0, mcfg.vocab_size, (2, self.P),
                               dtype=np.int32)
        with pytest.raises(ValueError, match="keyed for sharding"):
            generate(mcfg, params, cache.current_handle("t0"), scfg,
                     prompts, gen_len=2, max_len=self.ML,
                     adapter_cache=cache, mesh=mesh)

    def test_step_cache_is_bounded(self):
        mcfg, scfg, params, cache, server = self._setup()
        server.max_cached_steps = 2
        rng = np.random.default_rng(5)
        for n in range(3):           # three distinct bucket signatures
            prompts = rng.integers(0, mcfg.vocab_size, (2, self.P),
                                   dtype=np.int32)
            reqs = [Request(p, "t0") for p in prompts]
            server.serve(reqs, gen_len=1, max_len=self.ML + n)
        assert len(server._steps) == 2

    def test_mixed_prompt_lengths_route_through_engine(self):
        """Mixed-length batches are ADMITTED (continuous-batching engine,
        per-row prefill) — the legacy length-bucket error survives only
        on the forced static path. Full oracle coverage lives in
        tests/test_engine.py."""
        mcfg, scfg, params, cache, server = self._setup()
        rng = np.random.default_rng(7)
        reqs = [Request(rng.integers(0, mcfg.vocab_size, 6,
                                     dtype=np.int32), "t0"),
                Request(rng.integers(0, mcfg.vocab_size, 7,
                                     dtype=np.int32), "t1")]
        out = server.serve(reqs, gen_len=2, max_len=self.ML)
        assert isinstance(out, list)
        assert [len(o) for o in out] == [8, 9]
        with pytest.raises(ValueError, match="length bucket"):
            server.serve(reqs, gen_len=2, max_len=self.ML, static=True)


# ---------------------------------------------------------------------------
# Forced 2-device mesh (subprocess): grouped mixed batch vs sequential.
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


_MT_SPMD = """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import AdapterStateCache, DoRAConfig
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import MultiTenantServer, Request, generate
    from repro.launch.steps import StepConfig
    from repro.launch.train import build_state

    assert jax.device_count() == 2
    mesh = make_debug_mesh(2, 1)     # batch sharded over the data axis
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
    mcfg = get_config("qwen2-7b", smoke=True)
    scfg = StepConfig(dora=DCFG)
    params, _, _ = build_state(mcfg, DCFG, 0)
    cache = AdapterStateCache.for_serving(mcfg, scfg, mesh)
    assert cache.sharding == (("data", 2), ("model", 1))
    for t in range(3):
        _, ad, _ = build_state(mcfg, DCFG, 10 + t)
        cache.register(f"t{t}", ad)
    server = MultiTenantServer(mcfg, scfg, params, cache=cache, mesh=mesh)

    P, G, ML = 6, 3, 10
    rng = np.random.default_rng(0)
    reqs = []
    for t in range(3):
        for _ in range(2):
            reqs.append(Request(rng.integers(0, mcfg.vocab_size, P,
                                             dtype=np.int32), f"t{t}"))
    toks, logits = server.serve(reqs, gen_len=G, max_len=ML,
                                return_logits=True)
    toks = np.asarray(toks)
    for t in range(3):
        rows = [i for i, r in enumerate(reqs) if r.adapter == f"t{t}"]
        prompts = np.stack([np.asarray(reqs[i].prompt) for i in rows])
        st, sl = generate(mcfg, params, cache.current_handle(f"t{t}"),
                          scfg, prompts, gen_len=G, max_len=ML,
                          adapter_cache=cache, mesh=mesh,
                          return_logits=True)
        assert np.array_equal(np.asarray(st), toks[rows]), f"tokens t{t}"
        # To fp32 rounding, not bitwise: with the batch sharded over two
        # devices each 2-row tenant group is one row per device, and
        # XLA's 1-row matmul sums in another order than the grouped
        # batch's 3 rows per device (docs/numerics.md, caveat 3).
        for s in range(G):
            np.testing.assert_allclose(sl[s], logits[s][rows], rtol=1e-5,
                                       atol=1e-5, err_msg=f"t{t} step {s}")
    print("MT_SPMD_OK")
"""


@pytest.mark.slow
def test_multitenant_spmd_parity():
    """Acceptance on a forced 2-device CPU mesh: the grouped mixed batch
    (batch sharded over the data axis, per-tenant states precomputed and
    pinned through the mesh-aware cache) serves the tokens of per-tenant
    sequential serving under the same mesh, with fp32 logits equal to
    rounding."""
    out = _run_subprocess(_MT_SPMD, 2)
    assert "MT_SPMD_OK" in out, out
