"""Frozen-adapter serving state: bitwise cached-vs-recomputed g over a
multi-token decode, the zero-norm-work jaxpr assertion, the training
invalidation contract, the padded-prefill rewind, and the stacked-linear
kwarg forwarding.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.adapter as ad
from repro.configs import get_config
from repro.core import (DoRAConfig, dora_linear, dora_linear_stacked,
                        init_dora_params, invalidate_adapter_state,
                        precompute_adapter_state)
from repro.core.compose import magnitude_scale
from repro.core.factored_norm import dtype_eps
from repro.launch.steps import (StepConfig, make_decode_step,
                                make_precompute_step, make_prefill_step)
from repro.launch.train import build_state

ARCH = "phi4-mini-3.8b"


def _state(dcfg, seed=0):
    mcfg = get_config(ARCH, smoke=True)
    scfg = StepConfig(dora=dcfg)
    params, adapters, _ = build_state(mcfg, dcfg, seed)
    return mcfg, scfg, params, adapters


class TestCachedG:
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")

    def test_cached_g_bitwise_equals_recomputed(self):
        """The precomputed g leaf must be BITWISE the g the uncached
        forward computes (same norm route, same eps)."""
        mcfg, scfg, params, adapters = _state(self.DCFG)
        served = make_precompute_step(mcfg, scfg)(params, adapters)
        leaf = served["stack"]["l0"]["mixer"]["wq"]
        raw = adapters["stack"]["l0"]["mixer"]["wq"]
        W = params["stack"]["l0"]["mixer"]["wq"]
        for i in range(W.shape[0]):
            wn = ad.compute_weight_norm(W[i], raw["A"][i], raw["B"][i],
                                        scfg.dora)
            want = magnitude_scale(raw["m"][i], wn, dtype_eps(mcfg.dtype))
            np.testing.assert_array_equal(np.asarray(leaf["g"][i]),
                                          np.asarray(want))

    def test_decode_bitwise_cached_vs_recomputed(self):
        """Multi-token decode: logits with the cached-g tree must be
        bitwise identical to the per-token-norm path, token by token."""
        mcfg, scfg, params, adapters = _state(self.DCFG)
        served = jax.jit(make_precompute_step(mcfg, scfg))(params, adapters)
        B, P, L, G = 2, 6, 12, 4
        rng = np.random.default_rng(3)
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (B, P)),
                           jnp.int32)
        prefill = jax.jit(make_prefill_step(mcfg, scfg, None, batch=B,
                                            seq=L, padded=True))
        decode = jax.jit(make_decode_step(mcfg, scfg, None, batch=B))
        batch_in = {"tokens": jnp.pad(toks, ((0, 0), (0, L - P))),
                    "prompt_len": jnp.asarray(P, jnp.int32)}
        l_raw, c_raw = prefill(params, adapters, batch_in)
        l_srv, c_srv = prefill(params, served, batch_in)
        np.testing.assert_array_equal(np.asarray(l_raw), np.asarray(l_srv))
        for t in range(G):
            nxt = jnp.argmax(l_raw, axis=-1).astype(jnp.int32)[:, None]
            l_raw, c_raw = decode(params, adapters, c_raw, {"tokens": nxt})
            l_srv, c_srv = decode(params, served, c_srv, {"tokens": nxt})
            assert int(c_raw["len"]) == P + t + 1
            np.testing.assert_array_equal(np.asarray(l_raw),
                                          np.asarray(l_srv),
                                          err_msg=f"token {t}")

    def test_decode_jaxpr_has_zero_norm_work(self):
        """The acceptance-criteria trace assertion: the w_norm computation
        (tagged 'dora_wnorm') appears in the precompute and the uncached
        steps, and NOWHERE in prefill/decode once the state is cached."""
        mcfg, scfg, params, adapters = _state(self.DCFG)
        served = make_precompute_step(mcfg, scfg)(params, adapters)
        B, L = 2, 8
        from repro.models import init_cache
        cache = init_cache(mcfg, B, L)
        tok1 = jnp.zeros((B, 1), jnp.int32)
        tokP = jnp.zeros((B, L), jnp.int32)
        decode = make_decode_step(mcfg, scfg, None, batch=B)
        prefill = make_prefill_step(mcfg, scfg, None, batch=B, seq=L)
        pre_jaxpr = str(jax.make_jaxpr(make_precompute_step(mcfg, scfg))(
            params, adapters))
        assert "dora_wnorm" in pre_jaxpr
        assert "dora_wnorm" in str(jax.make_jaxpr(decode)(
            params, adapters, cache, {"tokens": tok1}))
        assert "dora_wnorm" not in str(jax.make_jaxpr(decode)(
            params, served, cache, {"tokens": tok1}))
        assert "dora_wnorm" not in str(jax.make_jaxpr(prefill)(
            params, served, {"tokens": tokP}))

    def test_training_refuses_cached_state(self):
        """Invalidation contract: a tree carrying serving state must be
        rejected by training call sites; stripping it restores training."""
        dcfg = self.DCFG
        key = jax.random.PRNGKey(0)
        W = jax.random.normal(key, (32, 64))
        x = jax.random.normal(jax.random.fold_in(key, 2), (4, 64))
        adp = init_dora_params(jax.random.fold_in(key, 1), W, dcfg)
        served = precompute_adapter_state(W, adp, dcfg)
        with pytest.raises(ValueError, match="invalid under training"):
            dora_linear(x, W, served, dcfg, training=True)
        y_srv = dora_linear(x, W, served, dcfg, training=False)
        stripped = invalidate_adapter_state(served)
        assert set(stripped.keys()) == set(adp.keys())
        y_raw = dora_linear(x, W, stripped, dcfg, training=True)
        np.testing.assert_allclose(np.asarray(y_srv), np.asarray(y_raw),
                                   rtol=1e-6, atol=1e-6)

    def test_precompute_step_with_mesh_pins_serving_shardings(self):
        """make_precompute_step(mesh=...) constrains the cached leaves to
        the serving shardings (gsB row-sharded like B); on the trivial
        1-device mesh the values are bitwise the unconstrained ones."""
        from repro.launch.mesh import make_debug_mesh
        mcfg, scfg, params, adapters = _state(self.DCFG)
        mesh = make_debug_mesh(1, 1)
        srv_m = jax.jit(make_precompute_step(mcfg, scfg, mesh,
                                             fold_gsb=True))(params,
                                                             adapters)
        srv_n = jax.jit(make_precompute_step(mcfg, scfg, None,
                                             fold_gsb=True))(params,
                                                             adapters)
        assert "gsB" in srv_m["stack"]["l0"]["mixer"]["wq"]
        assert jax.tree.structure(srv_m) == jax.tree.structure(srv_n)
        for a, b in zip(jax.tree.leaves(srv_m), jax.tree.leaves(srv_n)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fold_gsb_matches_unfolded(self):
        key = jax.random.PRNGKey(5)
        W = jax.random.normal(key, (128, 64))
        x = jax.random.normal(jax.random.fold_in(key, 2), (4, 64))
        adp = init_dora_params(jax.random.fold_in(key, 1), W, self.DCFG)
        adp["B"] = 0.2 * jax.random.normal(jax.random.fold_in(key, 3),
                                           adp["B"].shape)
        folded = precompute_adapter_state(W, adp, self.DCFG, fold_gsb=True)
        assert "gsB" in folded
        y_f = dora_linear(x, W, folded, self.DCFG, training=False)
        y_u = dora_linear(x, W, adp, self.DCFG, training=False)
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                                   rtol=1e-5, atol=1e-5)
        # re-precomputing a folded tree without folding must strip the
        # stale gsB (else the allclose-only path silently persists).
        refolded = precompute_adapter_state(W, folded, self.DCFG,
                                            fold_gsb=False)
        assert "gsB" not in refolded and "g" in refolded

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "fp32"])
    def test_folded_gsb_is_stored_in_the_activation_dtype(self, dtype):
        """The model-level precompute folds gsB in fp32 and stores it in
        the model's activation dtype: for a bf16 model, bitwise the fp32
        fold rounded once to bf16; for an fp32 model, bitwise the fp32
        fold. g stays fp32 either way."""
        mcfg = dataclasses.replace(get_config(ARCH, smoke=True), dtype=dtype)
        scfg = StepConfig(dora=self.DCFG)
        params, adapters, _ = build_state(mcfg, self.DCFG, 0)
        # seed-built trees have B == 0, which would fold to zeros
        key = jax.random.PRNGKey(21)
        paths, treedef = jax.tree_util.tree_flatten_with_path(adapters)
        adapters = jax.tree_util.tree_unflatten(treedef, [
            (0.2 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
             ).astype(leaf.dtype)
            if jax.tree_util.keystr(path).endswith("['B']") else leaf
            for i, (path, leaf) in enumerate(paths)])
        served = jax.jit(make_precompute_step(mcfg, scfg, fold_gsb=True))(
            params, adapters)
        folded = [n for n in jax.tree.leaves(
            served, is_leaf=lambda n: isinstance(n, dict) and "gsB" in n)
            if isinstance(n, dict)]
        assert folded
        for leaf in folded:
            assert leaf["gsB"].dtype == jnp.dtype(dtype)
            assert leaf["g"].dtype == jnp.float32
            fold32 = ((leaf["g"] * self.DCFG.scaling)[..., None]
                      * leaf["B"].astype(jnp.float32))
            assert np.any(np.asarray(fold32) != 0)
            np.testing.assert_array_equal(
                np.asarray(leaf["gsB"]), np.asarray(fold32.astype(dtype)))

    def test_gsb_fast_path_runs_under_sharding_constraint(self):
        """Sharded call sites used to fall off the broadcast-free decode
        compose (the constraint needed a y_lora to pin); with the
        rank-space constraint they take it too — on the trivial 1-device
        mesh the output is bitwise the unconstrained folded one."""
        from jax.sharding import PartitionSpec as P
        from repro.compat.mesh import make_mesh
        from repro.core.sharding import plan_for_output
        key = jax.random.PRNGKey(9)
        W = jax.random.normal(key, (128, 64))
        x = jax.random.normal(jax.random.fold_in(key, 2), (4, 64))
        adp = init_dora_params(jax.random.fold_in(key, 1), W, self.DCFG)
        adp["B"] = 0.2 * jax.random.normal(jax.random.fold_in(key, 3),
                                           adp["B"].shape)
        folded = precompute_adapter_state(W, adp, self.DCFG, fold_gsb=True)
        plan = plan_for_output(make_mesh((1,), ("model",)), P(None, "model"))
        y_c = jax.jit(lambda x: dora_linear(x, W, folded, self.DCFG,
                                            training=False,
                                            constrain=plan))(x)
        y_n = jax.jit(lambda x: dora_linear(x, W, folded, self.DCFG,
                                            training=False))(x)
        np.testing.assert_array_equal(np.asarray(y_c), np.asarray(y_n))


class TestPaddedPrefill:
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")

    def test_padded_prefill_matches_unpadded(self):
        """The serve.py:46 bug, fixed: padded prefill must return the
        logits of the TRUE last prompt token and rewind the cache to P —
        against an unpadded prefill, to fp32 rounding. prompt_len is TRACED, so one
        jitted prefill is reused across different P (shape-bucketing)."""
        mcfg, scfg, params, adapters = _state(self.DCFG)
        B, L = 2, 11
        rng = np.random.default_rng(7)
        pre_pad = jax.jit(make_prefill_step(mcfg, scfg, None, batch=B,
                                            seq=L, padded=True))
        decode = jax.jit(make_decode_step(mcfg, scfg, None, batch=B))
        for P in (5, 8):  # same compiled prefill serves both lengths
            toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (B, P)),
                               jnp.int32)
            pre_raw = jax.jit(make_prefill_step(mcfg, scfg, None, batch=B,
                                                seq=L))
            lp, cp = pre_pad(params, adapters,
                             {"tokens": jnp.pad(toks,
                                                ((0, 0), (0, L - P))),
                              "prompt_len": jnp.asarray(P, jnp.int32)})
            lr, cr = pre_raw(params, adapters, {"tokens": toks})
            assert int(cp["len"]) == P, "cache length not rewound to P"
            assert int(cr["len"]) == P
            # To fp32 rounding, not bitwise: XLA's CPU dot sums a matmul
            # over the padded B*L rows in another order than over B*P
            # (docs/numerics.md, caveat 4). A wrong gather position or an
            # unrewound length moves these by O(1).
            np.testing.assert_allclose(np.asarray(lp), np.asarray(lr),
                                       rtol=1e-5, atol=1e-5)
            # decode writes at position P: the first generated K/V row
            # lands there.
            nxt = jnp.argmax(lp, axis=-1).astype(jnp.int32)[:, None]
            _, cp2 = decode(params, adapters, cp, {"tokens": nxt})
            _, cr2 = decode(params, adapters, cr, {"tokens": nxt})
            assert int(cp2["len"]) == P + 1
            np.testing.assert_allclose(
                np.asarray(cp2["stack"]["l0"]["k"][:, :, P]),
                np.asarray(cr2["stack"]["l0"]["k"][:, :, P]),
                rtol=1e-5, atol=1e-5)
        assert pre_pad._cache_size() == 1, "padded prefill retraced per P"

    def test_padded_prefill_rejects_ssm_archs(self):
        mcfg = get_config("falcon-mamba-7b", smoke=True)
        scfg = StepConfig(dora=self.DCFG)
        with pytest.raises(ValueError, match="attention-only"):
            make_prefill_step(mcfg, scfg, None, batch=2, seq=8,
                              padded=True)

    def test_generate_end_to_end_padded_equals_exact(self):
        from repro.launch.serve import generate
        mcfg, scfg, params, adapters = _state(self.DCFG)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, mcfg.vocab_size, (2, 6), dtype=np.int32)
        t1 = np.asarray(generate(mcfg, params, adapters, scfg, prompts,
                                 gen_len=4, max_len=10))
        t2 = np.asarray(generate(mcfg, params, adapters, scfg, prompts,
                                 gen_len=4, max_len=10,
                                 cache_adapters=False))
        np.testing.assert_array_equal(t1, t2)


class TestDecodeLoopContract:
    """The prefill/decode cache-length contract: hard errors (the
    satellite keeps them), but behind a debug switch — the serving path
    no longer pays an int(cache['len']) device sync per prefill."""
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")

    def _loop_parts(self):
        from repro.launch.steps import make_decode_step, make_prefill_step
        mcfg, scfg, params, adapters = _state(self.DCFG)
        B, L = 2, 10
        prefill = jax.jit(make_prefill_step(mcfg, scfg, None, batch=B,
                                            seq=L, padded=True))
        decode = jax.jit(make_decode_step(mcfg, scfg, None, batch=B))
        rng = np.random.default_rng(11)
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (B, 6)),
                           jnp.int32)
        return params, adapters, prefill, decode, toks, L - 6

    def test_contract_violation_raises_when_checked(self):
        from repro.launch.serve import _decode_loop
        params, adapters, prefill, decode, toks, pad = self._loop_parts()

        def bad_prefill(p, a, b):
            logits, cache = prefill(p, a, b)
            return logits, {**cache, "len": cache["len"] + 1}

        with pytest.raises(RuntimeError, match="prefill left cache"):
            _decode_loop(bad_prefill, decode, params, adapters, toks,
                         prompt_len=6, gen_len=2, pad=pad, temperature=0.0,
                         seed=0, check_contract=True)

        def bad_decode(p, a, c, b):
            logits, cache = decode(p, a, c, b)
            return logits, {**cache, "len": cache["len"] - 1}

        with pytest.raises(RuntimeError, match="decode wrote at"):
            _decode_loop(prefill, bad_decode, params, adapters, toks,
                         prompt_len=6, gen_len=2, pad=pad, temperature=0.0,
                         seed=0, check_contract=True)

    def test_checks_off_by_default_no_host_sync(self, monkeypatch):
        """Default serving: the SAME violations pass through unchecked —
        proof the blocking int() sync is no longer on the hot path — and
        REPRO_SERVE_DEBUG=1 turns the guard back on without a code
        change."""
        from repro.launch.serve import _decode_loop
        monkeypatch.delenv("REPRO_SERVE_DEBUG", raising=False)
        params, adapters, prefill, decode, toks, pad = self._loop_parts()

        def bad_prefill(p, a, b):
            logits, cache = prefill(p, a, b)
            return logits, {**cache, "len": cache["len"] + 1}

        # violation NOT detected (check skipped)...
        out, _ = _decode_loop(bad_prefill, decode, params, adapters, toks,
                              prompt_len=6, gen_len=2, pad=pad,
                              temperature=0.0, seed=0)
        assert out.shape == (2, 8)
        # ...until the env switch re-enables the guard
        monkeypatch.setenv("REPRO_SERVE_DEBUG", "1")
        with pytest.raises(RuntimeError, match="prefill left cache"):
            _decode_loop(bad_prefill, decode, params, adapters, toks,
                         prompt_len=6, gen_len=2, pad=pad,
                         temperature=0.0, seed=0)

    def test_generate_forwards_check_contract(self):
        from repro.launch.serve import generate
        mcfg, scfg, params, adapters = _state(self.DCFG)
        rng = np.random.default_rng(12)
        prompts = rng.integers(0, mcfg.vocab_size, (2, 6), dtype=np.int32)
        t1 = np.asarray(generate(mcfg, params, adapters, scfg, prompts,
                                 gen_len=2, max_len=10,
                                 check_contract=True))
        t2 = np.asarray(generate(mcfg, params, adapters, scfg, prompts,
                                 gen_len=2, max_len=10,
                                 check_contract=False))
        np.testing.assert_array_equal(t1, t2)


class TestStackedKwargs:
    DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")

    def _stack(self, key, E=3, d_in=32, d_out=128):
        W = jax.random.normal(key, (E, d_out, d_in))
        x = jax.random.normal(jax.random.fold_in(key, 1), (E, 5, d_in))
        adp = init_dora_params(jax.random.fold_in(key, 2), W, self.DCFG)
        bias = jax.random.normal(jax.random.fold_in(key, 3), (E, d_out))
        return W, x, adp, bias

    def test_bias_and_training_forwarded(self):
        W, x, adp, bias = self._stack(jax.random.PRNGKey(13))
        y = dora_linear_stacked(x, W, adp, self.DCFG, bias=bias,
                                training=False)
        for e in range(W.shape[0]):
            ye = dora_linear(x[e], W[e],
                             jax.tree.map(lambda v: v[e], adp), self.DCFG,
                             bias=bias[e], training=False)
            np.testing.assert_allclose(np.asarray(y[e]), np.asarray(ye),
                                       rtol=1e-5, atol=1e-5)

    def test_base_sq_cache_forwarded_and_live(self):
        """A poisoned stacked cache must change the output — proves the
        kwarg actually reaches the per-slice norm fast path."""
        W, x, adp, _ = self._stack(jax.random.PRNGKey(14))
        adp["B"] = 0.2 * jax.random.normal(jax.random.PRNGKey(15),
                                           adp["B"].shape)
        base_sq = jnp.sum(W.astype(jnp.float32) ** 2, axis=2)
        y_ref = dora_linear_stacked(x, W, adp, self.DCFG)
        y_cached = dora_linear_stacked(x, W, adp, self.DCFG,
                                       base_sq_cache=base_sq)
        np.testing.assert_allclose(np.asarray(y_cached), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
        y_bad = dora_linear_stacked(x, W, adp, self.DCFG,
                                    base_sq_cache=base_sq * 4.0)
        assert not np.allclose(np.asarray(y_bad), np.asarray(y_ref))

    def test_stacked_serving_state(self):
        """Stacked leaves (experts) carry the cached g too."""
        W, x, adp, _ = self._stack(jax.random.PRNGKey(16))
        served = precompute_adapter_state(W, adp, self.DCFG)
        assert served["g"].shape == adp["m"].shape
        y_srv = dora_linear_stacked(x, W, served, self.DCFG,
                                    training=False)
        y_raw = dora_linear_stacked(x, W, adp, self.DCFG, training=False)
        np.testing.assert_array_equal(np.asarray(y_srv), np.asarray(y_raw))
