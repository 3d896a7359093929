"""Paged K/V gather for the block-paged decode cache.

The paged engine stores K/V in a per-layer block pool
``[n_blocks, block_size, Hkv, hd]`` with a per-slot block table
``pages [B, max_blocks]`` (int32 block ids, ``-1`` = unallocated). The
attention layer gathers the pool into the logical rectangular view
``[B, max_blocks * block_size, Hkv, hd]`` and then runs the UNCHANGED
per-row-frontier attention — bitwise parity with the rectangular cache is
by construction, because unallocated blocks read as exact zeros and every
position at or past a row's frontier is already masked to an exact 0.0
softmax weight by the causal bias.

Two tiers through :func:`repro.core.dispatch.plan_gather`:

  - ``paged_gather_ref`` — pure jnp (eager tier, and the oracle);
  - ``paged_gather`` — Pallas scalar-prefetch kernel: the block table is
    prefetched to SMEM and drives the pool BlockSpec index map, so each
    (row, table-slot) grid step DMAs exactly one ``[block_size, Hkv*hd]``
    block HBM→VMEM (unallocated slots clamp to block 0 and are zeroed in
    the body). Both tiers are pure copies + zero fill: bitwise identical.

The scatter back (:func:`paged_scatter`) is a jnp ``.at[].set`` on every
tier — XLA lowers it to an in-place dynamic-update when the pool is
donated, and the ``mode="drop"`` out-of-bounds rule gives the -1 → skip
semantics for free (all unallocated entries alias the same OOB id, so
``unique_indices`` must NOT be claimed).

The block table is a traced operand in both tiers: paging never
recompiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl, pltpu, resolve_interpret


def paged_gather_ref(pool, pages):
    """Gather ``pool [n_blocks, bs, Hkv, hd]`` through ``pages
    [B, max_blocks]`` into the logical ``[B, max_blocks*bs, Hkv, hd]``
    view; unallocated (-1) blocks read as zeros."""
    n_blocks, bs, hkv, hd = pool.shape
    b, mb = pages.shape
    valid = pages >= 0
    blocks = pool[jnp.maximum(pages, 0)]           # [B, mb, bs, Hkv, hd]
    blocks = jnp.where(valid[..., None, None, None], blocks,
                       jnp.zeros((), pool.dtype))
    return blocks.reshape(b, mb * bs, hkv, hd)


@functools.lru_cache(maxsize=None)
def _make_gather(n_blocks: int, bs: int, hd_flat: int, b: int, mb: int,
                 dtype_name: str, interpret: bool):
    """One pallas_call per (pool geometry, table geometry, dtype): the
    table VALUES are traced (scalar-prefetch), so paging never
    recompiles."""

    def _kernel(pages_ref, pool_ref, out_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)
        valid = pages_ref[i, j] >= 0
        out_ref[0, 0] = jnp.where(valid, pool_ref[0],
                                  jnp.zeros_like(pool_ref[0]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, mb),
        in_specs=[
            # One pool block per grid step, chosen BY the prefetched
            # table; -1 clamps to block 0 (zeroed in the body).
            pl.BlockSpec((1, bs, hd_flat),
                         lambda i, j, pages: (jnp.maximum(pages[i, j], 0),
                                              0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bs, hd_flat),
                               lambda i, j, pages: (i, j, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, mb, bs, hd_flat),
                                       jnp.dtype(dtype_name)),
        interpret=interpret,
        metadata={"kernel": "paged_gather"},
    )


def paged_gather(pool, pages, *, interpret: bool | None = None):
    """Pallas tier of :func:`paged_gather_ref` (bitwise-identical: both
    tiers are copies + zero fill). Requires ``Hkv*hd % 128 == 0`` — the
    dispatch plan (:func:`repro.core.dispatch.plan_gather`) enforces it."""
    interpret = resolve_interpret(interpret)
    n_blocks, bs, hkv, hd = pool.shape
    b, mb = pages.shape
    call = _make_gather(n_blocks, bs, hkv * hd, b, mb,
                        jnp.dtype(pool.dtype).name, interpret)
    out = call(pages.astype(jnp.int32), pool.reshape(n_blocks, bs,
                                                     hkv * hd))
    return out.reshape(b, mb * bs, hkv, hd)


def paged_scatter(pool, pages, values):
    """Write the logical ``values [B, max_blocks*bs, Hkv, hd]`` view back
    into ``pool`` through ``pages``; slices of unallocated (-1) blocks are
    dropped. Pure jnp on every tier (the scatter is a donate-friendly
    ``.at[].set`` that XLA updates in place)."""
    n_blocks, bs, hkv, hd = pool.shape
    b, mb = pages.shape
    vals = values.reshape(b * mb, bs, hkv, hd)
    # -1 → n_blocks: out of bounds, dropped. Every unallocated entry
    # aliases the SAME OOB id, so unique_indices would be a lie.
    ids = jnp.where(pages >= 0, pages, n_blocks).reshape(b * mb)
    return pool.at[ids].set(vals, mode="drop")
