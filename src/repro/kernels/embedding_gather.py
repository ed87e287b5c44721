"""Fused embedding gather + on-the-fly reduce — the Centaur *sparse engine*.

TPU adaptation of EB-Streamer (Fig. 10). The mapping is exact in spirit:

  SRAM_sparseID  -> scalar-prefetch operand: the index array lands in SMEM
                    *before* the grid starts, so the grid's BlockSpec
                    index_map can address arbitrary table rows, driving the
                    double-buffered HBM->VMEM DMA pipeline (the hardware
                    gather unit EB-GU becomes the Pallas pipeline engine);
  EB-RU          -> rows are accumulated into a VMEM fp32 accumulator as
                    they arrive (reduction happens on the fly; gathered rows
                    are never materialized to HBM);
  BPregs         -> the table Ref itself (base pointer + strides).

Unlike the CPU baseline (jnp take -> materialize (B, L, D) -> sum), this
kernel never materializes the gathered rows and writes D values per bag.

Training runs the same engine in reverse: ``sls_grad_table`` is the fused
segment *scatter-add* — the VJP of ``sparse_lengths_sum`` — streaming one
upstream bag-gradient row per grid step into the destination table row.
Positions are pre-sorted by destination so every output row is visited in
exactly one contiguous run (accumulate in VMEM, flush once), which is both
the output-stationary optimum and the only revisit pattern that is safe
under the TPU output-pipeline's deferred write-back.

Two TPU constraints shape every row kernel here (``Rows``, ``chunks``):

* Mosaic takes a block only when its last two dims are multiples of the
  (8, 128) tile or whole dims, so a lone (1, D) row is never a block. Each
  step streams the tile that holds its row, in the layout XLA already
  gives the table, and picks the row out of it exactly.
* The prefetched ids live in SMEM (1 MiB on v5e), so a long batch is split
  into several kernel calls over consecutive bags.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128        # minor tile dim of every TPU layout
SUBLANES = 8       # second-minor tile dim of a 32-bit layout
# int32 ids one kernel call may scalar-prefetch; SMEM is 1 MiB on v5e
SMEM_WORDS = 1 << 17


class Rows(NamedTuple):
    """How a kernel reaches one row of an (R, C) array without a relayout.

    On TPU, XLA stores an array whose rows are narrower than a vreg
    (C < 128) transposed, rows along lanes (``{0,1:T(8,128)}``), so that
    nothing is padded; wider arrays keep rows along sublanes. A kernel
    streams the block holding the row — (C, 128) of the transposed view, or
    (8, C) — and picks the row out with a mask. The pick is a sum of one
    value and zeros, so it is exact.
    """
    lanes: bool        # rows run along lanes: the array is stored transposed
    tile: int          # rows per block
    cols: int          # columns per block

    @classmethod
    def of(cls, rows: int, cols: int, block_cols: int = 0) -> "Rows":
        lanes = cols < LANES
        tile = LANES if lanes else SUBLANES
        return cls(lanes, min(rows, tile), block_cols or cols)

    def view(self, x: jax.Array) -> jax.Array:
        """The array as the kernel sees it, and back: a bitcast of the
        layout above."""
        return x.T if self.lanes else x

    def shape(self, rows: int, cols: int, dtype) -> jax.ShapeDtypeStruct:
        """An (rows, cols) output in the orientation ``view`` gives."""
        return jax.ShapeDtypeStruct((cols, rows) if self.lanes
                                    else (rows, cols), dtype)

    def spec(self, index_map) -> pl.BlockSpec:
        """Blocks of the tile holding a row; ``index_map`` returns
        (row, column block) as it would for the (R, C) array."""
        def index(*args):
            row, col = index_map(*args)
            return (col, row // self.tile) if self.lanes \
                else (row // self.tile, col)
        return pl.BlockSpec((self.cols, self.tile) if self.lanes
                            else (self.tile, self.cols), index)

    def scratch(self):
        """An f32 accumulator shaped like what ``read`` returns."""
        return pltpu.VMEM((self.cols, 1) if self.lanes else (1, self.cols),
                          jnp.float32)

    def _hit(self, ref, row):
        axis = 1 if self.lanes else 0
        return jax.lax.broadcasted_iota(jnp.int32, ref.shape, axis) \
            == row % self.tile

    def read(self, ref, row) -> jax.Array:
        """Row ``row`` of the block in ``ref`` as f32 (cols, 1) or (1, cols)."""
        x = ref[...]
        if jnp.issubdtype(x.dtype, jnp.integer):
            x = x.astype(jnp.int32)     # Mosaic casts no narrow int to f32
        x = jnp.where(self._hit(ref, row), x.astype(jnp.float32), 0.0)
        return jnp.sum(x, axis=1 if self.lanes else 0, keepdims=True)

    def write(self, ref, row, val) -> None:
        """Store ``val`` as row ``row`` of the block in ``ref``."""
        ref[...] = jnp.where(self._hit(ref, row), val.astype(ref.dtype),
                             ref[...])


def per_call(words_each: int) -> int:
    """Bags (or positions) one call takes when each prefetches
    ``words_each`` int32 ids into SMEM."""
    return max(1, SMEM_WORDS // max(1, words_each))


def chunks(n: int, words_each: int):
    """[start, stop) ranges over n bags, one kernel call each."""
    step = per_call(words_each)
    return [(s, min(s + step, n)) for s in range(0, n, step)]


def arbitrary(n_axes: int) -> pltpu.CompilerParams:
    """Sequential grid: the row walks revisit accumulators step to step."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * n_axes)


def _bag_kernel(idx_ref, table_ref, o_ref, acc_ref, *, n_l: int, tab: Rows,
                out: Rows):
    b = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One gathered row arrives per grid step (streamed HBM->VMEM by the
    # pipeline using the prefetched index); reduce it immediately.
    acc_ref[...] += tab.read(table_ref, idx_ref[b * n_l + l])

    @pl.when(l == n_l - 1)
    def _flush():
        out.write(o_ref, b, acc_ref[...])


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def embedding_bag(table: jax.Array, indices: jax.Array, *, bd: int = 2048,
                  interpret: bool = False) -> jax.Array:
    """Fixed-lookup SparseLengthsSum: out[b] = sum_l table[idx[b, l]].

    table: (V, D), indices: (B, L) int32 -> (B, D).
    Grid: (bags, d-blocks, lookups); lookups innermost so the fp32
    accumulator tile is revisited on consecutive steps (output-stationary).
    """
    v, d = table.shape
    b, l = indices.shape
    bd = min(bd, d)
    tab = Rows.of(v, d, bd)
    parts = []
    for s, e in chunks(b, l):
        out = Rows.of(e - s, d, bd)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e - s, pl.cdiv(d, bd), l),
            # the tile holding the row the prefetched sparse index names
            # — the EB-GU address generator
            in_specs=[tab.spec(lambda bb, dd, ll, idx: (idx[bb * l + ll],
                                                        dd))],
            out_specs=out.spec(lambda bb, dd, ll, idx: (bb, dd)),
            scratch_shapes=[tab.scratch()],
        )
        fn = pl.pallas_call(
            functools.partial(_bag_kernel, n_l=l, tab=tab, out=out),
            grid_spec=grid_spec,
            out_shape=out.shape(e - s, d, table.dtype),
            compiler_params=arbitrary(3),
            interpret=interpret,
        )
        parts.append(out.view(fn(indices[s:e].reshape(-1), tab.view(table))))
    return jnp.concatenate(parts)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def gather_rows(table: jax.Array, indices: jax.Array, *, bd: int = 2048,
                interpret: bool = False) -> jax.Array:
    """Plain row gather (L=1 bags): out[t] = table[indices[t]].

    Used for LM vocab-embedding lookup (single-row 'bags'); same streaming
    engine without the reduction stage.
    """
    return embedding_bag(table, indices[:, None], bd=bd, interpret=interpret)


def _ragged_row(idx, off, bb, ll):
    """Row of lookup ll of bag bb; out-of-bag steps are routed to row 0."""
    pos = off[bb] + ll
    safe = jnp.minimum(pos, idx.shape[0] - 1)
    return jnp.where(pos < off[bb + 1], idx[safe], 0)


def _ragged_kernel(idx_ref, off_ref, table_ref, o_ref, acc_ref, *,
                   max_l: int, tab: Rows, out: Rows):
    l = pl.program_id(2)
    b = pl.program_id(0)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Ragged bags: lookup j of bag b is valid iff off[b]+j < off[b+1].
    # Invalid steps were routed to row 0 by the index_map; mask them here
    # (the EB-GU issuing a no-op gather — the pipeline still double-buffers).
    valid = off_ref[b] + l < off_ref[b + 1]
    row = tab.read(table_ref, _ragged_row(idx_ref, off_ref, b, l))
    acc_ref[...] += jnp.where(valid, row, 0.0)

    @pl.when(l == max_l - 1)
    def _flush():
        out.write(o_ref, b, acc_ref[...])


@functools.partial(jax.jit, static_argnames=("max_l", "interpret"))
def sparse_lengths_sum(table: jax.Array, indices: jax.Array,
                       offsets: jax.Array, *, max_l: int,
                       interpret: bool = False) -> jax.Array:
    """Ragged SparseLengthsSum — the paper's Fig. 2 API, in one kernel.

    table (V, D); indices (L,) int32; offsets (B+1,) int32 (bag b reads
    indices[offsets[b]:offsets[b+1]]); max_l = static max bag length.
    Both scalar arrays are prefetched to SMEM (SRAM_sparseID + the offset
    half of BPregs); the gather address is computed per grid step as
    idx[off[b] + l] with out-of-bag steps masked in the reduction.
    """
    v, d = table.shape
    b = offsets.shape[0] - 1
    tab = Rows.of(v, d)
    spans = chunks(b, max_l + 1)
    if len(spans) > 1:
        # each call takes its bags' window of the stream, re-based; the
        # zero tail keeps every window in bounds
        padded = jnp.concatenate([indices, jnp.zeros(
            per_call(max_l + 1) * max_l, indices.dtype)])
    parts = []
    for s, e in spans:
        idx, off = indices, offsets
        if len(spans) > 1:
            off = offsets[s:e + 1] - offsets[s]
            idx = jax.lax.dynamic_slice(padded, (offsets[s],),
                                        ((e - s) * max_l,))
        out = Rows.of(e - s, d)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e - s, 1, max_l),
            in_specs=[tab.spec(lambda bb, dd, ll, i, o: (
                _ragged_row(i, o, bb, ll), dd))],
            out_specs=out.spec(lambda bb, dd, ll, i, o: (bb, dd)),
            scratch_shapes=[tab.scratch()],
        )
        fn = pl.pallas_call(
            functools.partial(_ragged_kernel, max_l=max_l, tab=tab, out=out),
            grid_spec=grid_spec,
            out_shape=out.shape(e - s, d, table.dtype),
            compiler_params=arbitrary(3),
            interpret=interpret,
        )
        parts.append(out.view(fn(idx, off, tab.view(table))))
    return jnp.concatenate(parts)


def _grad_kernel(dst_ref, bag_ref, val_ref, g_ref, init_ref, o_ref, acc_ref,
                 *, n: int, grad: Rows, tab: Rows):
    p = pl.program_id(0)
    row = dst_ref[p]
    prev = dst_ref[jnp.maximum(p - 1, 0)]

    @pl.when((p == 0) | (prev // tab.tile != row // tab.tile))
    def _open():
        # rows of this tile that no run visits keep what the table held
        o_ref[...] = init_ref[...]

    @pl.when((p == 0) | (prev != row))
    def _init():
        # zero, or the partial sum an earlier call left for a run it split
        acc_ref[...] = tab.read(init_ref, row)

    # One upstream bag-gradient row arrives per step (streamed by the
    # pipeline via the prefetched bag id); out-of-bag padding adds zero.
    g = grad.read(g_ref, bag_ref[p])
    acc_ref[...] += jnp.where(val_ref[p] > 0, g, 0.0)

    nxt = dst_ref[jnp.minimum(p + 1, n - 1)]

    @pl.when((p == n - 1) | (nxt != row))
    def _flush():
        tab.write(o_ref, row, acc_ref[...])


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def sls_grad_table(g: jax.Array, indices: jax.Array, offsets: jax.Array, *,
                   n_rows: int, interpret: bool = False) -> jax.Array:
    """Fused segment scatter-add: the VJP of ``sparse_lengths_sum``.

    g (B, D) upstream bag gradients; indices (N,) destination rows (may be
    padded past offsets[-1]); offsets (B+1,). Returns d_table (n_rows, D):
    ``d_table[r] = sum over valid positions p with indices[p] == r of
    g[bag(p)]``.

    Positions are argsorted by destination row, so duplicate targets form
    one contiguous run per row: the run accumulates in a VMEM register and
    flushes exactly once. Untouched rows come from a zero table aliased
    onto the output buffer (``input_output_aliases``) — the kernel writes
    only the tiles a run visits, everything else stays zero without a
    separate (n_rows, D) clearing pass. Longer streams take several calls
    threading the same table; a run split between two calls resumes from
    the partial sum the first left in the table.
    """
    n = indices.shape[0]
    n_bags = offsets.shape[0] - 1
    d = g.shape[-1]
    if n == 0:
        return jnp.zeros((n_rows, d), g.dtype)
    pos = jnp.arange(n, dtype=offsets.dtype)
    seg = jnp.searchsorted(offsets[1:], pos, side="right")
    valid = (pos < offsets[-1]).astype(jnp.int32)
    order = jnp.argsort(indices)
    dst = indices[order].astype(jnp.int32)
    bag = jnp.minimum(seg, n_bags - 1)[order].astype(jnp.int32)
    val = valid[order]

    grad, tab = Rows.of(n_bags, d), Rows.of(n_rows, d)
    c = min(n, per_call(3))
    k = pl.cdiv(n, c)
    if k > 1:
        # equal calls over the sorted stream; the pad extends the last run
        # with zero-weight positions, which leave it unchanged
        pad = k * c - n
        dst = jnp.concatenate([dst, jnp.broadcast_to(dst[-1:], (pad,))])
        bag = jnp.pad(bag, (0, pad))
        val = jnp.pad(val, (0, pad))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(c,),
        in_specs=[
            grad.spec(lambda p, dst, bag, val: (bag[p], 0)),
            tab.spec(lambda p, dst, bag, val: (dst[p], 0)),
        ],
        out_specs=tab.spec(lambda p, dst, bag, val: (dst[p], 0)),
        scratch_shapes=[tab.scratch()],
    )
    fn = pl.pallas_call(
        functools.partial(_grad_kernel, n=c, grad=grad, tab=tab),
        grid_spec=grid_spec,
        out_shape=tab.shape(n_rows, d, g.dtype),
        # operand 4 = the table (after 3 scalar-prefetch operands and g)
        input_output_aliases={4: 0},
        compiler_params=arbitrary(1),
        interpret=interpret,
    )
    g_view = grad.view(g)

    def call(i, table):
        part = [jax.lax.dynamic_slice(x, (i * c,), (c,))
                for x in (dst, bag, val)]
        return fn(*part, g_view, table)

    table = jnp.zeros(tab.shape(n_rows, d, g.dtype).shape, g.dtype)
    table = call(0, table) if k == 1 else \
        jax.lax.fori_loop(0, k, call, table)
    return tab.view(table)
