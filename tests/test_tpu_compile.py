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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dora_compose as ck
from repro.kernels.factored_norm import norm_terms_pallas
from repro.kernels.norm_assembly import assemble_norm_pallas
from repro.kernels.paged_gather import paged_gather

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
