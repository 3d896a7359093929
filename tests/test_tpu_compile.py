"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at qwen2-7b widths (d_model 3584, d_ff 18944,
GQA K/V 4 x 128) and the paper's rank r=384, in bf16, for a described
v5e chip that is not attached, and asserts the TPU compiler accepted it
as a Mosaic custom call. This catches what interpret mode cannot: block
shapes the TPU tiling refuses and kernels that overflow VMEM.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and under pytest-xdist
only the worker that runs this file may do so.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dora_compose as ck
from repro.kernels.factored_norm import norm_terms_pallas
from repro.kernels.norm_assembly import assemble_norm_pallas
from repro.kernels.paged_gather import paged_gather
from repro.kernels.selective_scan import (selective_scan_bwd_pallas,
                                          selective_scan_pallas)

D_MODEL, D_FF, KV_HEADS, HEAD_DIM, RANK = 3584, 18944, 4, 128, 384
ROWS = 4096            # one 4k training sequence
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it. And
    # the kernels are written for 32-bit indices, whatever an earlier
    # test in this process left jax_enable_x64 at.
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield desc
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text: str, name: str) -> None:
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f'"kernel":"{name}"' in text


def test_compose_fwd_dual_output(one_chip):
    bn = ck.pick_block_n(D_FF, 1024)
    text = _compiled_text(
        lambda b, l, g: ck.compose_fwd_pallas(
            b, l, g, 9.8, save_inner=True, block_m=256, block_n=bn),
        one_chip, ((ROWS, D_FF), BF16), ((ROWS, D_FF), BF16),
        ((1, D_FF), F32))
    _assert_kernel(text, "compose_fwd_pallas")


def test_compose_bwd(one_chip):
    bn = ck.pick_block_n(D_FF, 1024)
    text = _compiled_text(
        lambda dy, gm1, gs: ck.compose_bwd_pallas(
            dy, gm1, gs, block_m=256, block_n=bn),
        one_chip, ((ROWS, D_FF), BF16), ((1, D_FF), F32), ((1, D_FF), F32))
    _assert_kernel(text, "compose_bwd_pallas")


@pytest.mark.parametrize("rows,block_m", [(ROWS, 256), (8, 8)],
                         ids=["train_block256", "decode_block8"])
def test_compose_mm_fwd(one_chip, rows, block_m):
    bn = ck.pick_block_n(D_MODEL, 1024)
    text = _compiled_text(
        lambda b, h, B, g: ck.compose_mm_fwd_pallas(
            b, h, B, g, 9.8, block_m=block_m, block_n=bn),
        one_chip, ((rows, D_MODEL), BF16), ((rows, RANK), BF16),
        ((D_MODEL, RANK), BF16), ((1, D_MODEL), F32))
    _assert_kernel(text, "compose_mm_fwd_pallas")


def test_compose_mm_bwd(one_chip):
    bn = ck.pick_block_n(D_MODEL, 1024)
    text = _compiled_text(
        lambda dy, B, gm1, gs: ck.compose_mm_bwd_pallas(
            dy, B, gm1, gs, block_m=256, block_n=bn),
        one_chip, ((ROWS, D_MODEL), BF16), ((D_MODEL, RANK), BF16),
        ((1, D_MODEL), F32), ((1, D_MODEL), F32))
    _assert_kernel(text, "compose_mm_bwd_pallas")


# Jamba2-3B's selective scan: d_inner 5120, state 16, one 4096-token block
# of the mixer's training core (``models.mamba.SEQ_BLOCK``), chunk 128.
SCAN_DI, SCAN_N, SCAN_CHUNK = 5120, 16, 128


def test_selective_scan_fwd(one_chip):
    rows = ((1, ROWS, SCAN_DI), F32)
    text = _compiled_text(
        lambda dt, dtx, b, c, a, h0: selective_scan_pallas(
            dt, dtx, b, c, a, h0, chunk=SCAN_CHUNK, save_states=True,
            interpret=False),
        one_chip, rows, rows, ((1, ROWS, SCAN_N), F32),
        ((1, ROWS, SCAN_N), F32), ((SCAN_N, SCAN_DI), F32),
        ((1, SCAN_N, SCAN_DI), F32))
    _assert_kernel(text, "selective_scan_pallas")


def test_selective_scan_bwd(one_chip):
    rows = ((1, ROWS, SCAN_DI), F32)
    state = ((1, SCAN_N, SCAN_DI), F32)
    text = _compiled_text(
        lambda dt, dtx, b, c, a, hs, dy, dh: selective_scan_bwd_pallas(
            dt, dtx, b, c, a, hs, dy, dh, chunk=SCAN_CHUNK,
            interpret=False),
        one_chip, rows, rows, ((1, ROWS, SCAN_N), F32),
        ((1, ROWS, SCAN_N), F32), ((SCAN_N, SCAN_DI), F32),
        ((1, ROWS // SCAN_CHUNK, SCAN_N, SCAN_DI), F32), rows, state)
    _assert_kernel(text, "selective_scan_bwd_pallas")


def test_norm_terms(one_chip):
    text = _compiled_text(
        lambda W, A, B: norm_terms_pallas(W, A, B, block_rows=256,
                                          block_k=512),
        one_chip, ((D_FF, D_MODEL), BF16), ((RANK, D_MODEL), BF16),
        ((D_FF, RANK), BF16))
    _assert_kernel(text, "norm_terms_pallas")


def test_assemble_norm(one_chip):
    text = _compiled_text(
        lambda b, c, ba: assemble_norm_pallas(b, c, ba, 9.8),
        one_chip, ((D_FF,), F32), ((D_FF,), F32), ((D_FF,), F32))
    _assert_kernel(text, "assemble_norm_pallas")


def test_paged_gather(one_chip):
    # 8 decode slots x a 2048-token window in 16-token blocks.
    slots, max_len, bs = 8, 2048, 16
    n_blocks = slots * max_len // bs
    text = _compiled_text(
        lambda pool, pages: paged_gather(pool, pages, interpret=False),
        one_chip, ((n_blocks, bs, KV_HEADS, HEAD_DIM), BF16),
        ((slots, max_len // bs), jnp.int32))
    _assert_kernel(text, "paged_gather")


def _entry_converts(text: str) -> list[str]:
    """Output shapes (``bf16[2,8,3584,384]``) of the ``convert`` ops at the
    top level of a compiled module's entry computation."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    return re.findall(r"= (\w+\[[\d,]*\])\S* convert\(", entry)


def test_fleet_decode_reads_the_stack_without_converting_it(one_chip):
    """The fleet decode step (K-lane stack of folded serving states, one
    traced lane index per row) for a bf16 model, at qwen2-7b widths with
    two scanned layers. The folded ``gsB`` is stored in the activation
    dtype, so the step feeds the stack to its dot as it is: no entry-level
    ``convert`` rewrites a ``[n_scan, K, d_out, r]`` stack on every
    token."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import DoRAConfig, stack_adapter_states
    from repro.launch.steps import (StepConfig, make_decode_step,
                                    make_precompute_step)
    from repro.models.lm import adapter_shapes, cache_shapes, param_shapes

    lanes, slots, max_len = 8, 8, 256
    mcfg = dataclasses.replace(get_config("qwen2-7b"), num_layers=2,
                               vocab_size=4096, dtype=BF16)
    scfg = StepConfig(dora=DoRAConfig(rank=RANK, alpha=192.0, rslora=True))
    params = param_shapes(mcfg)
    state = jax.eval_shape(
        make_precompute_step(mcfg, scfg, fold_gsb=True), params,
        adapter_shapes(mcfg, scfg.dora))
    stack = jax.eval_shape(
        lambda s: stack_adapter_states([s] * lanes, axis=1), state)
    gsb = {tuple(l.shape) for l in jax.tree.leaves(
        jax.tree.map(lambda leaf: leaf["gsB"], stack["stack"],
                     is_leaf=lambda n: isinstance(n, dict) and "gsB" in n))}
    assert gsb == {(2, lanes, d, RANK)
                   for d in (D_MODEL, D_FF, KV_HEADS * HEAD_DIM)}, gsb
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    batch_in = {"tokens": jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                "adapter_idx": jax.ShapeDtypeStruct((slots,), jnp.int32)}
    text = jax.jit(make_decode_step(mcfg, scfg, batch=slots,
                                    dynamic_groups=True)).lower(
        on_chip(params), on_chip(stack),
        on_chip(cache_shapes(mcfg, slots, max_len, row_lens=True)),
        on_chip(batch_in)).compile().as_text()
    stack_dims = {",".join(map(str, shape)) for shape in gsb}
    stack_shaped = [s for s in _entry_converts(text)
                    if s[s.index("[") + 1:-1] in stack_dims]
    assert not stack_shaped, (
        f"the decode step converts the fleet stack's folded gsB on every "
        f"token: {stack_shaped}")
