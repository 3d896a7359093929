"""The benchmark's plain float32 reference against the program run in
float32 at a tiny size (CPU): the two must agree to float32 rounding, so
that at the cells' sizes every gap the checks read is the program's bf16
arithmetic, not a difference in what is computed."""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import model  # noqa: E402
import program  # noqa: E402
import reference as R  # noqa: E402

CFG = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
       "rope_theta": 10000.0, "torch_dtype": "float32", "qkv_bias": True,
       "dora": {"rank": 8, "alpha": 16.0, "rslora": True},
       "program": {"attn_chunk": 1024, "remat": "layer",
                   "dora_mode": "eager"}}
SEED = 2 ** 40 + 3



@pytest.fixture(autouse=True)
def _x64_off():
    """Other test modules turn on 64-bit types when they are imported; the
    benchmark runs with JAX's default 32-bit types."""
    import jax
    with jax.enable_x64(False):
        yield

def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_head_stats_in_blocks_equal_the_whole_head(quant):
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (7, 16))
    head = jax.random.normal(jax.random.fold_in(k, 1),
                             (250, 16)).astype(jnp.bfloat16)
    t = jnp.array([0, 249, 100, 99, 200, 5, 150])
    lse, gold, best, arg = R.head_stats({"head": head}, x, t, quant,
                                        block=64)   # last block overlaps
    lg = R._mm(x, head.astype(jnp.float32), quant)
    np.testing.assert_allclose(lse, jax.scipy.special.logsumexp(lg, -1),
                               rtol=1e-5)
    np.testing.assert_allclose(gold, lg[jnp.arange(7), t], rtol=1e-5)
    np.testing.assert_allclose(best, lg.max(-1), rtol=1e-5)
    assert (arg == lg.argmax(-1)).all()


@pytest.mark.parametrize("rotary", [1.0, 0.75])
def test_reference_logits_equal_the_programs_in_float32(rotary):
    from repro.models import forward
    cfg = dict(CFG, partial_rotary_factor=rotary)
    d = model.dims(cfg)
    params, (ad,) = model.make_weights(d, SEED, 1)
    tokens = np.random.default_rng(0).integers(0, 256, 40, dtype=np.int32)
    mcfg = program.model_config(cfg)
    scfg = program.step_config(cfg)
    got, _, _ = forward(mcfg, f32(params), ad, scfg.dora,
                        tokens=jnp.asarray(tokens)[None], training=False)
    with jax.default_matmul_precision("highest"):
        x = R.hidden(d, params, ad, jnp.asarray(tokens))
        want = R._mm(x, params["head"].astype(jnp.float32), None)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_reference_step_equals_the_programs_in_float32():
    import gen
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    import json
    tr = json.loads((ROOT / "benchmarks" / "chip" / "traffic" /
                     "train-4k.json").read_text())
    tr = dict(tr, seq=32, loss_tokens=8)
    d = model.dims(CFG)
    params, (ad,) = model.make_weights(d, SEED, 1)
    b = gen.TrainStream(vocab=256, seq=32, batch=1, seed=SEED).batch_np(0)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    step = make_train_step(program.model_config(CFG),
                           program.step_config(CFG, tr), None, batch=1,
                           seq=32)
    a32 = f32(ad)
    new, _, metrics = jax.jit(step)(f32(params), a32, adamw_init(a32), batch)
    ref_a, mom = R.start_training(ad)
    ref_new, _, loss, _ = R.train_step(
        d, ref_a, mom, params, batch["tokens"], batch["labels"],
        tr["loss_tokens"], tuple(sorted(tr["optimizer"].items())), None)
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)
    for (p, x), (_, y) in zip(model.adapter_leaves(new),
                              model.adapter_leaves(ref_new)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6,
                                   err_msg=p)


def test_a_tied_configuration_serves_the_embedding_as_its_head():
    d = model.dims(dict(CFG, tie_word_embeddings=True))
    params, _ = model.make_weights(d, SEED, 1)
    assert d.tied and np.array_equal(np.asarray(params["head"]),
                                     np.asarray(params["embed"]))
    untied, _ = model.make_weights(model.dims(CFG), SEED, 1)
    assert not np.array_equal(np.asarray(untied["head"]),
                              np.asarray(untied["embed"]))
    # the other weights are drawn alike either way
    assert np.array_equal(np.asarray(untied["embed"]),
                          np.asarray(params["embed"]))
