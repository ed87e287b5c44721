"""jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU the compiled Pallas kernels run; on any other
backend the mathematically identical XLA path from ``ref.py`` runs so the
framework is usable end-to-end, and tests exercise the kernel bodies with
``interpret=True``. The active implementation can be forced globally:

    from repro.kernels import ops
    ops.set_impl("interpret")   # 'auto' | 'xla' | 'pallas' | 'interpret'

``embedding_bag`` carries a custom VJP: the backward of a gather-reduce is a
scatter-add into the table — the sparse engine run in reverse — implemented
with XLA scatter (segment-sum semantics), keeping training differentiable
through the kernel path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import embedding_gather as _eg
from repro.kernels import feature_interaction as _fi
from repro.kernels import fused_dispatch as _fd
from repro.kernels import gemm as _gm
from repro.kernels import ref as _ref

_IMPL = "auto"
_VALID = ("auto", "xla", "pallas", "interpret")


def set_impl(impl: str) -> None:
    global _IMPL
    if impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}")
    _IMPL = impl


def get_impl() -> str:
    if _IMPL != "auto":
        return _IMPL
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# GEMM (dense engine)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def gemm(x: jax.Array, w: jax.Array) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.gemm(x, w)
    return _gm.gemm(x, w, interpret=(impl == "interpret"))


def _gemm_fwd(x, w):
    return gemm(x, w), (x, w)


def _gemm_bwd(res, g):
    # backward-of-GEMM = two GEMMs on the same engine (dx = g w^T,
    # dw = x^T g), so training runs the dense engine end to end
    x, w = res
    dx = gemm(g, w.T).astype(x.dtype)
    dw = gemm(x.T, g).astype(w.dtype)
    return dx, dw


gemm.defvjp(_gemm_fwd, _gemm_bwd)


# ---------------------------------------------------------------------------
# Embedding bag (sparse engine) with custom VJP
# ---------------------------------------------------------------------------

import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bag(table: jax.Array, indices: jax.Array, vocab: int,
         dtype_name: str) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.embedding_bag(table, indices)
    return _eg.embedding_bag(table, indices, interpret=(impl == "interpret"))


def _bag_fwd(table, indices, vocab, dtype_name):
    return _bag(table, indices, vocab, dtype_name), indices


def _bag_bwd(vocab, dtype_name, indices, g):
    b, l = indices.shape
    d = g.shape[-1]
    g32 = g.astype(jnp.float32)
    g_rows = jnp.broadcast_to(g32[:, None, :], (b, l, d))
    d_table = jnp.zeros((vocab, d), jnp.float32)
    d_table = d_table.at[indices.reshape(-1)].add(g_rows.reshape(b * l, d))
    return d_table.astype(dtype_name), None


_bag.defvjp(_bag_fwd, _bag_bwd)


def embedding_bag(table: jax.Array, indices: jax.Array) -> jax.Array:
    """out[b] = sum_l table[indices[b, l]]; table (V,D), indices (B,L)."""
    return _bag(table, indices, table.shape[0], str(table.dtype))


def gather_rows(table: jax.Array, indices: jax.Array) -> jax.Array:
    """out[t] = table[indices[t]]; single-row bags (LM token embedding)."""
    return embedding_bag(table, indices[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _sls(table: jax.Array, indices: jax.Array, offsets: jax.Array,
         max_l: int, vocab: int, dtype_name: str) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.sparse_lengths_sum(table, indices, offsets)
    return _eg.sparse_lengths_sum(table, indices, offsets, max_l=max_l,
                                  interpret=(impl == "interpret"))


def _sls_fwd(table, indices, offsets, max_l, vocab, dtype_name):
    return _sls(table, indices, offsets, max_l, vocab, dtype_name), \
        (indices, offsets)


def _sls_bwd(max_l, vocab, dtype_name, res, g):
    indices, offsets = res
    impl = get_impl()
    if impl == "xla":
        d_table = _ref.sls_grad_table(g, indices, offsets, vocab)
    else:
        d_table = _eg.sls_grad_table(g, indices, offsets, n_rows=vocab,
                                     interpret=(impl == "interpret"))
    return d_table.astype(dtype_name), None, None


_sls.defvjp(_sls_fwd, _sls_bwd)


def sparse_lengths_sum(table: jax.Array, indices: jax.Array,
                       offsets: jax.Array, *, max_l: int) -> jax.Array:
    """Ragged SparseLengthsSum (the paper's Fig. 2 production API).

    out[b] = sum over table[indices[offsets[b]:offsets[b+1]]]; indices may
    be padded past offsets[-1] (padded positions are ignored). `max_l` is
    the static per-bag length bound the kernel grid is sized for.

    Differentiable on every backend: the custom VJP is the fused segment
    scatter-add (the sparse engine run in reverse) — the Pallas
    `sls_grad_table` kernel on pallas/interpret, the XLA segment-sum
    reference on xla.
    """
    return _sls(table, indices, offsets, max_l, table.shape[0],
                str(table.dtype))


# ---------------------------------------------------------------------------
# Fused segmented dispatch (sparse engine, dense id-matrix form)
# ---------------------------------------------------------------------------

def _dense_offsets(dense_ids: jax.Array) -> jax.Array:
    # A dense (B, L) id matrix IS a uniform-offset ragged stream, so the
    # fused backward can reuse the proven sls_grad_table scatter-add.
    b, l = dense_ids.shape
    return jnp.arange(b + 1, dtype=jnp.int32) * l


def _dense_grad_table(g, dense_ids, vocab, null_row):
    impl = get_impl()
    offsets = _dense_offsets(dense_ids)
    if impl == "xla":
        d = _ref.sls_grad_table(g, dense_ids.reshape(-1), offsets, vocab)
    else:
        d = _eg.sls_grad_table(g, dense_ids.reshape(-1), offsets,
                               n_rows=vocab,
                               interpret=(impl == "interpret"))
    if null_row is None:
        return d
    # null_row is the always-zero sentinel every short/padded dense slot
    # points at. The ragged backward never trained it (fill lived past
    # offsets[-1] and was masked); the dense relayout moves fill INSIDE
    # the stream, so pin its gradient to zero here or the dense optimizer
    # would break the sentinel's always-zero invariant on the first step.
    return d.at[null_row].set(0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused(table: jax.Array, dense_ids: jax.Array, vocab: int,
           dtype_name: str, null_row) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.fused_segment_sum(table, dense_ids)
    return _fd.fused_segment_sum(table, dense_ids,
                                 interpret=(impl == "interpret"))


def _fused_fwd(table, dense_ids, vocab, dtype_name, null_row):
    return _fused(table, dense_ids, vocab, dtype_name, null_row), dense_ids


def _fused_bwd(vocab, dtype_name, null_row, dense_ids, g):
    d = _dense_grad_table(g, dense_ids, vocab, null_row)
    return d.astype(dtype_name), None


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_segment_sum(table: jax.Array, dense_ids: jax.Array, *,
                      null_row=None) -> jax.Array:
    """Segmented reduce over a dense id matrix: out[b] = sum_j table[ids[b,j]].

    ``dense_ids`` is a ``se.ragged_dense_ids`` relayout — (B, max_l) with
    short/padded slots pointing at an always-zero row — so the whole
    reduction is one gather + one per-bag sum with NO scatter in the
    forward. Returns f32 (B, D). Differentiable: the custom VJP is the
    same fused segment scatter-add backing ``sparse_lengths_sum``; pass
    ``null_row`` (the sentinel the relayout's fill slots point at) so the
    backward pins its gradient to zero like the ragged tail mask did.
    """
    return _fused(table, dense_ids, table.shape[0], str(table.dtype),
                  None if null_row is None else int(null_row))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused_cached(hot_rows: jax.Array, arena: jax.Array, slots: jax.Array,
                  cold_ids: jax.Array, k_slots: int, vocab: int,
                  hot_dtype: str, arena_dtype: str,
                  null_row) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.fused_cached_segment_sum(hot_rows, arena, slots, cold_ids)
    return _fd.fused_cached_segment_sum(hot_rows, arena, slots, cold_ids,
                                        interpret=(impl == "interpret"))


def _fused_cached_fwd(hot_rows, arena, slots, cold_ids, k_slots, vocab,
                      hot_dtype, arena_dtype, null_row):
    out = _fused_cached(hot_rows, arena, slots, cold_ids, k_slots, vocab,
                        hot_dtype, arena_dtype, null_row)
    return out, (slots, cold_ids)


def _fused_cached_bwd(k_slots, vocab, hot_dtype, arena_dtype, null_row,
                      res, g):
    slots, cold_ids = res
    # the hot arena's null slot is its last row by HotRowCache
    # construction (k real slots + one zero miss slot)
    d_hot = _dense_grad_table(g, slots, k_slots,
                              k_slots - 1).astype(hot_dtype)
    d_arena = _dense_grad_table(g, cold_ids, vocab,
                                null_row).astype(arena_dtype)
    return d_hot, d_arena, None, None


_fused_cached.defvjp(_fused_cached_fwd, _fused_cached_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_cached_coh(hot_rows, arena, dense_ids, slots, cold_ids,
                      k_slots, vocab, hot_dtype, arena_dtype, null_row):
    impl = get_impl()
    if impl == "xla":
        # Coherence law (docs/ARCHITECTURE.md §2): hot copies equal their
        # arena rows, so hot_rows[slot] + arena[cold_id] == arena[id] per
        # position. XLA's gather cost ignores locality, which makes the
        # plain arena reduction the fastest correct lowering — the hit
        # test survives only in the backward, where the hot/cold grad
        # split is real state.
        return _ref.fused_segment_sum(arena, dense_ids)
    # On the accelerator the two-table walk IS the win: hot rows live in
    # SRAM, so the in-kernel hit test turns arena HBM traffic into
    # on-chip loads for every cached row.
    return _fd.fused_cached_segment_sum(hot_rows, arena, slots, cold_ids,
                                        interpret=(impl == "interpret"))


def _fused_cached_coh_fwd(hot_rows, arena, dense_ids, slots, cold_ids,
                          k_slots, vocab, hot_dtype, arena_dtype, null_row):
    out = _fused_cached_coh(hot_rows, arena, dense_ids, slots, cold_ids,
                            k_slots, vocab, hot_dtype, arena_dtype, null_row)
    return out, (slots, cold_ids)


def _fused_cached_coh_bwd(k_slots, vocab, hot_dtype, arena_dtype, null_row,
                          res, g):
    slots, cold_ids = res
    d_hot = _dense_grad_table(g, slots, k_slots,
                              k_slots - 1).astype(hot_dtype)
    d_arena = _dense_grad_table(g, cold_ids, vocab,
                                null_row).astype(arena_dtype)
    return d_hot, d_arena, None, None, None


_fused_cached_coh.defvjp(_fused_cached_coh_fwd, _fused_cached_coh_bwd)


def fused_cached_segment_sum(hot_rows: jax.Array, arena: jax.Array,
                             slots: jax.Array, cold_ids: jax.Array, *,
                             dense_ids=None, null_row=None) -> jax.Array:
    """One-pass hot/cold segmented reduce with the hit test in the kernel.

    Per position exactly one of ``hot_rows[slots]`` (miss -> the zero
    null slot) and ``arena[cold_ids]`` (hit -> the zero null row) is
    nonzero, so accumulating their sum in a single walk equals the
    uncached reduction bit-for-bit — replacing CachedSource's two full
    passes. slots/cold_ids are (B, max_l) dense matrices over the same
    bags. Returns f32 (B, D); gradients flow to both arenas via the
    fused segment scatter-add, with the miss slot's and (when
    ``null_row`` is given) the arena sentinel's gradients pinned to zero.

    When ``dense_ids`` (the pre-split id matrix) is also given, the op
    additionally assumes the cache coherence law — hot copies equal
    their arena rows — and on the XLA substrate lowers the forward to
    the plain arena reduction (one gather, identical to the uncached
    path), since per-row gather cost there is locality-blind. The
    backward is unchanged: gradients still split onto hot slots and
    cold ids exactly. The Pallas lowering always runs the real
    two-table walk. Omit ``dense_ids`` when coherence is not
    guaranteed (e.g. deliberately stale hot rows).
    """
    if dense_ids is not None:
        return _fused_cached_coh(hot_rows, arena, dense_ids, slots,
                                 cold_ids, hot_rows.shape[0],
                                 arena.shape[0], str(hot_rows.dtype),
                                 str(arena.dtype),
                                 None if null_row is None else int(null_row))
    return _fused_cached(hot_rows, arena, slots, cold_ids,
                         hot_rows.shape[0], arena.shape[0],
                         str(hot_rows.dtype), str(arena.dtype),
                         None if null_row is None else int(null_row))


# ---------------------------------------------------------------------------
# Int4 cold tier (sparse engine, nibble-packed arena)
# ---------------------------------------------------------------------------

def int4_pack(a32: jax.Array):
    """Row-wise symmetric int4 quantize + nibble-pack.

    Per-row scale = amax/7 (the int8 rule at 4 bits), all-zero rows get a
    zero scale — the null-row masking protocol carries straight through.
    Returns (packed uint8 (R, ceil(D/2)), scales f32 (R, 1)).
    """
    return _ref.int4_pack(a32)


def int4_unpack(packed: jax.Array, scales: jax.Array, dim: int) -> jax.Array:
    """Dequantize an ``int4_pack`` arena back to f32 (R, dim)."""
    return _ref.int4_unpack(packed, scales, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_int4(packed: jax.Array, scales: jax.Array, dense_ids: jax.Array,
                dim: int) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.fused_int4_segment_sum(packed, scales, dense_ids, dim)
    return _fd.fused_int4_segment_sum(packed, scales, dense_ids, dim=dim,
                                      interpret=(impl == "interpret"))


def _fused_int4_fwd(packed, scales, dense_ids, dim):
    return _fused_int4(packed, scales, dense_ids, dim), \
        (packed, dense_ids)


def _fused_int4_bwd(dim, res, g):
    packed, dense_ids = res
    # out[b] = sum_j codes[ids[b,j]] * scales[ids[b,j]], so the only
    # trainable leaf is scales: d_scales[r] = sum over positions p with
    # id_p == r of <g[bag(p)], codes[r]>. The packed codes are integers
    # (None cotangent, like every integer arg in this module), and a null
    # row's codes are all zero so its scale gradient is automatically
    # zero — no sentinel pinning needed.
    b, max_l = dense_ids.shape
    g32 = g.astype(jnp.float32)                              # (B, dim)
    codes = _ref._int4_codes(packed[dense_ids], dim)         # (B, L, dim)
    per_pos = jnp.einsum("bld,bd->bl", codes.astype(jnp.float32), g32)
    d_scales = jnp.zeros((packed.shape[0], 1), jnp.float32)
    d_scales = d_scales.at[dense_ids.reshape(-1), 0].add(per_pos.reshape(-1))
    return None, d_scales, None


_fused_int4.defvjp(_fused_int4_fwd, _fused_int4_bwd)


def fused_int4_segment_sum(packed: jax.Array, scales: jax.Array,
                           dense_ids: jax.Array, *, dim: int) -> jax.Array:
    """Fused int4 dequantize-in-the-gather reduce over a dense id matrix.

    packed (V, ceil(dim/2)) uint8 + scales (V, 1) f32 from ``int4_pack``;
    dense_ids (B, max_l) with fill slots pointing at a zero-scale row.
    Returns f32 (B, dim) at an eighth of the fp32 gather bytes.
    Differentiable in ``scales`` only (the codes are frozen integers) —
    enough for the tiered property suite; cold-tier rows are trained via
    the fp shadow in the online trainer, not through this op.
    """
    return _fused_int4(packed, scales, dense_ids, int(dim))


# ---------------------------------------------------------------------------
# Feature interaction (dense engine, batched GEMM)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def interaction(x: jax.Array) -> jax.Array:
    impl = get_impl()
    if impl == "xla":
        return _ref.interaction(x)
    return _fi.interaction(x, interpret=(impl == "interpret"))


def _interaction_fwd(x):
    return interaction(x), x


def _interaction_bwd(x, g):
    # z = X X^T per sample => dX = (G + G^T) X
    g32 = g.astype(jnp.float32)
    sym = g32 + jnp.swapaxes(g32, -1, -2)
    dx = jnp.einsum("bfg,bgd->bfd", sym, x.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    return (dx.astype(x.dtype),)


interaction.defvjp(_interaction_fwd, _interaction_bwd)


def interaction_tril(x: jax.Array) -> jax.Array:
    """DLRM interaction: lower-triangle (offset -1) of X X^T, flattened."""
    z = interaction(x)
    f = x.shape[1]
    li, lj = jnp.tril_indices(f, k=-1)
    return z[:, li, lj]
