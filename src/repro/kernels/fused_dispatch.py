"""Fused segmented dispatch — ONE kernel for grouped / cached / sharded
lookups.

The flexible sparse paths (heterogeneous table groups, the hot-row cache,
the row-sharded cold pass) all used to re-walk the full interleaved index
stream once per component: T full-stream reductions for a T-table group,
two full passes for a hot/cold split. This module is the kernel half of
the fix: the stream is relayouted ONCE into a dense (n_bags, max_l) id
matrix (``se.ragged_dense_ids`` — position j of bag b, short/padded slots
pointing at an always-zero row), and each consumer walks only its own
bags' rows, accumulating every bag's reduction in a VMEM register tile.

Two kernels:

* ``fused_segment_sum`` — the segmented gather-reduce over a dense id
  matrix. Per-table base offsets are already folded into the ids (the
  BPregs add happens at relayout time), so a table group runs one of
  these per member over a (B, max_l) *slice* of the shared matrix
  instead of a full-stream reduction each.
* ``fused_cached_segment_sum`` — the same walk with the hot/cold hit
  test *inside* the kernel: each step gathers the hot slot row (miss ->
  zero null slot) and the cold arena row (hit -> zero null row) and
  accumulates their sum, so hot + cold costs ONE pass and equals the
  uncached reduction bit-for-bit (exactly one term per step is nonzero).

The custom VJP lives in ``ops``: a dense id matrix is a uniform-offset
ragged stream, so the backward IS the existing ``sls_grad_table`` fused
segment scatter-add — training through the fused path reuses the proven
gradient kernel unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.embedding_gather import Rows, arbitrary, chunks


def _fused_kernel(ids_ref, table_ref, o_ref, acc_ref, *, max_l: int,
                  tab: Rows, out: Rows):
    b = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One gathered row per grid step, row chosen by the prefetched dense
    # id; fill slots point at the always-zero null row, so the reduction
    # needs no validity mask at all.
    acc_ref[...] += tab.read(table_ref, ids_ref[b * max_l + l])

    @pl.when(l == max_l - 1)
    def _flush():
        out.write(o_ref, b, acc_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_segment_sum(table: jax.Array, dense_ids: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """Segmented gather-reduce over a ``ragged_dense_ids`` matrix.

    table (V, D); dense_ids (B, max_l) int32 with short/padded slots
    pointing at an always-zero row. Returns f32 (B, D):
    ``out[b] = sum_j table[dense_ids[b, j]]``.
    """
    v, d = table.shape
    b, max_l = dense_ids.shape
    if max_l == 0:
        return jnp.zeros((b, d), jnp.float32)
    tab = Rows.of(v, d)
    parts = []
    for s, e in chunks(b, max_l):
        out = Rows.of(e - s, d)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e - s, 1, max_l),
            in_specs=[
                tab.spec(lambda bb, dd, ll, ids: (ids[bb * max_l + ll], dd)),
            ],
            out_specs=out.spec(lambda bb, dd, ll, ids: (bb, dd)),
            scratch_shapes=[tab.scratch()],
        )
        fn = pl.pallas_call(
            functools.partial(_fused_kernel, max_l=max_l, tab=tab, out=out),
            name="fused_segment_sum",
            grid_spec=grid_spec,
            out_shape=out.shape(e - s, d, jnp.float32),
            compiler_params=arbitrary(3),
            interpret=interpret,
        )
        parts.append(out.view(fn(dense_ids[s:e].reshape(-1),
                                 tab.view(table))))
    return jnp.concatenate(parts)


def _cached_kernel(slots_ref, cold_ref, hot_ref, arena_ref, o_ref, acc_ref,
                   *, max_l: int, hot: Rows, tab: Rows, out: Rows):
    b = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The in-kernel hit test: per step exactly one of the two gathered
    # rows is nonzero (a miss reads the hot arena's zero null slot, a hit
    # reads the cold arena's zero null row), so accumulating their sum is
    # bit-for-bit the uncached reduction — in ONE pass.
    i = b * max_l + l
    acc_ref[...] += hot.read(hot_ref, slots_ref[i]) \
        + tab.read(arena_ref, cold_ref[i])

    @pl.when(l == max_l - 1)
    def _flush():
        out.write(o_ref, b, acc_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_cached_segment_sum(hot_rows: jax.Array, arena: jax.Array,
                             slots: jax.Array, cold_ids: jax.Array, *,
                             interpret: bool = False) -> jax.Array:
    """One-pass hot/cold segmented reduce (the in-kernel hit test).

    hot_rows (K+1, D) with slot K always zero; arena (V, D) with the null
    row always zero; slots/cold_ids (B, max_l) the dense hot-slot and
    redirected cold-row matrices of the same bags. Returns f32 (B, D)
    equal to ``fused_segment_sum(hot_rows, slots) +
    fused_segment_sum(arena, cold_ids)`` computed in a single walk.
    """
    v, d = arena.shape
    b, max_l = slots.shape
    assert cold_ids.shape == slots.shape, (cold_ids.shape, slots.shape)
    assert hot_rows.shape[1] == d, (hot_rows.shape, arena.shape)
    if max_l == 0:
        return jnp.zeros((b, d), jnp.float32)
    hot, tab = Rows.of(hot_rows.shape[0], d), Rows.of(v, d)
    parts = []
    for s, e in chunks(b, 2 * max_l):
        out = Rows.of(e - s, d)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e - s, 1, max_l),
            in_specs=[
                hot.spec(lambda bb, dd, ll, sl, co: (sl[bb * max_l + ll],
                                                     dd)),
                tab.spec(lambda bb, dd, ll, sl, co: (co[bb * max_l + ll],
                                                     dd)),
            ],
            out_specs=out.spec(lambda bb, dd, ll, sl, co: (bb, dd)),
            scratch_shapes=[tab.scratch()],
        )
        fn = pl.pallas_call(
            functools.partial(_cached_kernel, max_l=max_l, hot=hot, tab=tab,
                              out=out),
            name="fused_cached_segment_sum",
            grid_spec=grid_spec,
            out_shape=out.shape(e - s, d, jnp.float32),
            compiler_params=arbitrary(3),
            interpret=interpret,
        )
        parts.append(out.view(fn(slots[s:e].reshape(-1),
                                 cold_ids[s:e].reshape(-1),
                                 hot.view(hot_rows), tab.view(arena))))
    return jnp.concatenate(parts)


def _int4_kernel(ids_ref, packed_ref, scales_ref, lo_ref, hi_ref, lo_acc,
                 hi_acc, *, max_l: int, tab: Rows, sc: Rows, out: Rows):
    b = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        lo_acc[...] = jnp.zeros_like(lo_acc)
        hi_acc[...] = jnp.zeros_like(hi_acc)

    # Unpack the gathered row's nibbles in-register: biased codes (q+8,
    # 8 == zero), byte k holding dims 2k (low) and 2k+1 (high). A null
    # row's scale is zero, so fill slots contribute nothing — same
    # masking-free walk as the fp kernel, at an eighth of the gather bytes.
    # Even and odd dims accumulate apart and are interleaved by the caller.
    row = ids_ref[b * max_l + l]
    p = tab.read(packed_ref, row).astype(jnp.int32)
    scale = sc.read(scales_ref, row)
    lo_acc[...] += ((p & 0xF) - 8).astype(jnp.float32) * scale
    hi_acc[...] += ((p >> 4) - 8).astype(jnp.float32) * scale

    @pl.when(l == max_l - 1)
    def _flush():
        out.write(lo_ref, b, lo_acc[...])
        out.write(hi_ref, b, hi_acc[...])


@functools.partial(jax.jit, static_argnames=("dim", "interpret"))
def fused_int4_segment_sum(packed: jax.Array, scales: jax.Array,
                           dense_ids: jax.Array, *, dim: int,
                           interpret: bool = False) -> jax.Array:
    """Fused int4 dequantize-in-the-gather segmented reduce.

    packed (V, ceil(dim/2)) uint8 nibble pairs + scales (V, 1) f32 from
    ``ref.int4_pack``; dense_ids (B, max_l) with short/padded slots
    pointing at a zero-scale row. Returns f32 (B, dim):
    ``out[b] = sum_j unpack(packed)[dense_ids[b, j]]``.
    """
    v, p = packed.shape
    assert scales.shape == (v, 1), (scales.shape, packed.shape)
    assert p * 2 >= dim > (p - 1) * 2, (p, dim)
    b, max_l = dense_ids.shape
    if max_l == 0:
        return jnp.zeros((b, dim), jnp.float32)
    tab, sc = Rows.of(v, p), Rows.of(v, 1)
    parts = []
    for s, e in chunks(b, max_l):
        out = Rows.of(e - s, p)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e - s, 1, max_l),
            in_specs=[
                tab.spec(lambda bb, dd, ll, ids: (ids[bb * max_l + ll], dd)),
                sc.spec(lambda bb, dd, ll, ids: (ids[bb * max_l + ll], dd)),
            ],
            out_specs=[out.spec(lambda bb, dd, ll, ids: (bb, dd))] * 2,
            scratch_shapes=[tab.scratch(), tab.scratch()],
        )
        fn = pl.pallas_call(
            functools.partial(_int4_kernel, max_l=max_l, tab=tab, sc=sc,
                              out=out),
            name="fused_int4_segment_sum",
            grid_spec=grid_spec,
            out_shape=[out.shape(e - s, p, jnp.float32)] * 2,
            compiler_params=arbitrary(3),
            interpret=interpret,
        )
        lo, hi = fn(dense_ids[s:e].reshape(-1), tab.view(packed),
                    sc.view(scales))
        codes = jnp.stack([out.view(lo), out.view(hi)], axis=-1)
        parts.append(codes.reshape(e - s, 2 * p)[:, :dim])
    return jnp.concatenate(parts)
