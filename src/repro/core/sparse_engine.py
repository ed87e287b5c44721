"""The Centaur sparse engine: multi-table embedding gather/reduce.

The paper's EB-Streamer (Fig. 10) is reproduced structurally:

* **BPregs** — every embedding table lives at a base offset inside one flat
  row *arena* ``(total_rows + 1, D)``; the engine's address generator turns a
  (table, row) pair into ``base[t] + row`` exactly like the paper's
  base-pointer + offset logic. The final arena row is an always-zero row used
  as the null target for masked / out-of-shard lookups, which keeps the
  *fused on-the-fly reduction* kernel applicable even on the sharded path.
* **SRAM_sparseID / EB-GU / EB-RU** — the Pallas kernel in
  ``repro.kernels.embedding_gather`` (scalar-prefetched indices driving
  streaming row DMAs with in-VMEM reduction).
* **Shared-memory direct access** — on a pod, the "CPU DIMMs holding the
  tables" become the pod-wide HBM pool: the arena is **row-sharded across the
  'model' mesh axis**; each chip reduces the rows it owns and a single psum
  combines partial bags. Only reduced D-vectors ever cross chips (the same
  reason Centaur streams reductions instead of raw gathered rows).

NOTE: the lookup entry points that used to live here (``lookup``,
``lookup_sharded``, ``lookup_auto``, ``lookup_quantized``, and the six
``lookup_ragged*`` variants) are deprecation shims now — the unified API
is ``repro.core.embedding_source``: one ``lookup_bags`` / ``lookup_fixed``
pair dispatching over pytree-registered ``EmbeddingSource`` values. This
module keeps the arena layout (ArenaSpec / flatten), the shard-local
reduction protocol, and the hot-row cache data structures those sources
are built from.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _deprecated(name: str, repl: str) -> None:
    warnings.warn(
        f"repro.core.sparse_engine.{name} is deprecated; use "
        f"repro.core.embedding_source.{repl} over an EmbeddingSource "
        f"(see the README migration table)",
        DeprecationWarning, stacklevel=3)


@dataclass(frozen=True)
class ArenaSpec:
    """Static description of the embedding arena (the BPregs contents)."""
    n_tables: int
    rows_per_table: int
    dim: int
    dtype: str = "float32"

    @property
    def total_rows(self) -> int:
        # +1: trailing always-zero null row for masked lookups
        return self.n_tables * self.rows_per_table + 1

    @property
    def null_row(self) -> int:
        return self.n_tables * self.rows_per_table

    def padded_rows(self, shards: int) -> int:
        """Arena rows padded so the row dim divides the model axis."""
        r = self.total_rows
        return ((r + shards - 1) // shards) * shards


def init_arena(key: jax.Array, spec: ArenaSpec, shards: int = 1,
               scale: float = 0.01) -> jax.Array:
    """Arena of all tables, null row zeroed, padded for `shards` row-shards."""
    rows = spec.padded_rows(shards)
    arena = scale * jax.random.normal(key, (rows, spec.dim), jnp.float32)
    arena = arena.at[spec.null_row:].set(0.0)
    return arena.astype(spec.dtype)


def flatten_indices(spec: ArenaSpec, indices: jax.Array) -> jax.Array:
    """(B, T, L) per-table row ids -> (B*T, L) arena row ids (base + offset)."""
    b, t, l = indices.shape
    base = (jnp.arange(t, dtype=indices.dtype) * spec.rows_per_table)
    flat = indices + base[None, :, None]
    return flat.reshape(b * t, l)


def lookup(arena: jax.Array, spec: ArenaSpec, indices: jax.Array) -> jax.Array:
    """DEPRECATED shim: use ``lookup_fixed(FpArena(arena), spec, indices)``."""
    from repro.core import embedding_source as es
    _deprecated("lookup", "lookup_fixed(FpArena(arena), ...)")
    return es.lookup_fixed(es.FpArena(arena), spec, indices)


def lookup_sharded(arena_shard: jax.Array, spec: ArenaSpec,
                   indices: jax.Array, axis: str) -> jax.Array:
    """DEPRECATED shim: shard-local fixed reduce, now
    ``FpArena(arena_shard).shard_reduce_fixed`` (inside shard_map)."""
    from repro.core import embedding_source as es
    _deprecated("lookup_sharded",
                "ShardedArena(FpArena(arena), mesh) with lookup_fixed")
    b, t, _ = indices.shape
    flat = flatten_indices(spec, indices)
    part = es.FpArena(arena_shard).shard_reduce_fixed(spec, flat, axis)
    return part.reshape(b, t, spec.dim).astype(arena_shard.dtype)


def lookup_auto(arena: jax.Array, spec: ArenaSpec, indices: jax.Array,
                mesh: Optional[jax.sharding.Mesh] = None,
                axis: str = "model") -> jax.Array:
    """DEPRECATED shim: use ``lookup_fixed(resolve_source(arena, mesh))``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_auto", "lookup_fixed(resolve_source(arena, mesh))")
    return es.lookup_fixed(es.resolve_source(arena, mesh, axis), spec,
                           indices)


def quantize_arena(arena: jax.Array):
    """Row-wise symmetric int8 quantization of the embedding arena.

    The paper's core capacity constraint (tables of 100s of GB must live in
    commodity memory) motivates this beyond-paper lever: int8 rows + one f32
    scale per row = 3.9x capacity, dequantized on the fly inside the gather
    (the EB-RU reduces dequantized rows; a zero scale keeps the null row
    inert). Returns (q int8 (R, D), scales f32 (R, 1)).
    """
    return _rowwise_quantize(arena.astype(jnp.float32))


def _rowwise_quantize(a32: jax.Array):
    """THE row-wise symmetric int8 rule — the single definition shared by
    the full-arena build and the incremental `quantize_rows` patch, so
    the patch stays bit-identical to a full rebuild by construction."""
    amax = jnp.max(jnp.abs(a32), axis=-1, keepdims=True)
    scales = amax / 127.0
    q = jnp.where(scales > 0,
                  jnp.clip(jnp.round(a32 / jnp.maximum(scales, 1e-30)),
                           -127, 127), 0).astype(jnp.int8)
    return q, scales


def lookup_quantized(q: jax.Array, scales: jax.Array, spec: ArenaSpec,
                     indices: jax.Array) -> jax.Array:
    """DEPRECATED shim: use ``lookup_fixed(QuantizedArena(q, scales), …)``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_quantized",
                "lookup_fixed(QuantizedArena(q, scales), ...)")
    return es.lookup_fixed(es.QuantizedArena(q, scales), spec, indices)


# ---------------------------------------------------------------------------
# Ragged production path (paper Fig. 2: SparseLengthsSum over ragged bags)
#
# Batch layout: bags are ordered (sample, table) row-major — bag k holds
# sample k // n_tables, table k % n_tables. `indices` is the flat stream of
# per-table row ids for all bags concatenated, possibly padded past
# offsets[-1] (padding is inert); `offsets` has B*T+1 entries.
# ---------------------------------------------------------------------------

def ragged_segment_ids(offsets: jax.Array, n: int) -> jax.Array:
    """Bag id per index position; positions >= offsets[-1] get n_bags."""
    return jnp.searchsorted(offsets[1:], jnp.arange(n, dtype=offsets.dtype),
                            side="right")


def ragged_position_tables(offsets: jax.Array, n: int, n_tables: int):
    """(owning table, validity) per flat stream position — THE single
    encoding of the (sample, table) row-major bag convention. Every
    consumer that routes stream positions to tables (the grouped lookup,
    its per-table row grads, touched-row accounting for cache patching)
    must share this one definition or they silently desync."""
    n_bags = offsets.shape[0] - 1
    seg = ragged_segment_ids(offsets, n)
    table = jnp.minimum(seg, n_bags - 1) % n_tables
    return table, seg < n_bags


def ragged_dense_ids(indices: jax.Array, offsets: jax.Array, *,
                     max_l: int, fill) -> jax.Array:
    """Relayout a ragged id stream into a static (n_bags, max_l) matrix.

    ``dense[b, j] = indices[offsets[b] + j]`` for j inside bag b, `fill`
    elsewhere (short bags and the padded tail). This is THE layout step of
    the fused segmented dispatch: done once per batch, it turns every
    downstream reduction into a mask-free gather + per-bag sum — no
    scatter ever appears in the forward HLO, and the hot/cold and grouped
    paths all consume the same matrix. `max_l` must bound every bag's
    length (the same contract the Pallas grid already imposes); with
    `fill` pointing at an always-zero row the result needs no masking.
    """
    n_bags = offsets.shape[0] - 1
    if indices.shape[0] == 0 or max_l == 0:
        return jnp.full((n_bags, max_l), fill, indices.dtype)
    dense, valid = _ragged_slots(indices, offsets, max_l)
    return jnp.where(valid, dense, jnp.asarray(fill, indices.dtype))


def _ragged_slots(indices: jax.Array, offsets: jax.Array, max_l: int):
    """(ids, valid) of the (n_bags, max_l) relayout: the stream's id at
    slot j of bag b (clamped into the stream where the slot is outside
    the bag), and whether the slot lies inside the bag."""
    pos = offsets[:-1, None] + jnp.arange(max_l, dtype=offsets.dtype)
    valid = pos < offsets[1:, None]
    safe = jnp.minimum(jnp.where(valid, pos, 0), indices.shape[0] - 1)
    return jnp.take(indices, safe, axis=0), valid


def dense_arena_ids(spec: ArenaSpec, indices: jax.Array,
                    offsets: jax.Array, *, max_l: int) -> jax.Array:
    """Per-table ragged stream -> (n_bags, max_l) arena row ids.

    The relayout of ``ragged_dense_ids`` with each row's table base
    added, and every short or padded slot at the zero null row. Row b of
    the matrix IS bag b, so its table is ``b % n_tables`` (the (sample,
    table) row-major convention of ``ragged_position_tables``) and no
    position has to search for its bag. Bit-equal to
    ``ragged_dense_ids(flatten_ragged_indices(spec, indices, offsets),
    offsets, max_l=max_l, fill=spec.null_row)``.
    """
    n_bags = offsets.shape[0] - 1
    null = jnp.asarray(spec.null_row, indices.dtype)
    if indices.shape[0] == 0 or max_l == 0:
        return jnp.full((n_bags, max_l), null, indices.dtype)
    dense, valid = _ragged_slots(indices, offsets, max_l)
    base = ((jnp.arange(n_bags, dtype=indices.dtype) % spec.n_tables)
            * spec.rows_per_table)
    return jnp.where(valid, dense + base[:, None], null)


def dense_partial_reduce(arena_shard: jax.Array, dense: jax.Array,
                         axis: str, *, null_row=None) -> jax.Array:
    """Shard-local half of the fused dense reduce (inside shard_map):
    gather the owned rows of a ``ragged_dense_ids`` matrix, zero-mask the
    foreign ones, one per-bag sum, one psum — the sharded cold pass
    without per-shard segment scatters. Returns f32 (n_bags, D).

    Pass ``null_row`` so the always-zero sentinel the relayout's fill
    slots point at is masked like a foreign row: the forward is unchanged
    (the row is zero) but autodiff then gives it zero gradient, matching
    the ragged path where fill lived past offsets[-1]."""
    lo, vlocal = shard_row_range(arena_shard, axis)
    return _masked_fixed_partial_reduce(
        lambda safe: jnp.take(arena_shard, safe, axis=0)
        .astype(jnp.float32), lo, vlocal, dense, axis,
        null_row=null_row)


def flatten_ragged_indices(spec: ArenaSpec, indices: jax.Array,
                           offsets: jax.Array) -> jax.Array:
    """Per-table row ids (N,) -> arena row ids (N,) (base + offset).

    The owning table of each position follows from its bag id; padded tail
    positions are routed to the always-zero null row so every downstream
    consumer (kernel, cache, quantized reduce) stays mask-free.
    """
    table, valid = ragged_position_tables(offsets, indices.shape[0],
                                          spec.n_tables)
    flat = indices + table.astype(indices.dtype) * spec.rows_per_table
    return jnp.where(valid, flat,
                     jnp.asarray(spec.null_row, indices.dtype))


def lookup_ragged(arena: jax.Array, spec: ArenaSpec, indices: jax.Array,
                  offsets: jax.Array, *, max_l: int) -> jax.Array:
    """DEPRECATED shim: use ``lookup_bags(FpArena(arena), ...)``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_ragged", "lookup_bags(FpArena(arena), ...)")
    return es.lookup_bags(es.FpArena(arena), spec, indices, offsets,
                          max_l=max_l)


def shard_row_range(arena_shard: jax.Array, axis: str):
    """(lo, vlocal) of the contiguous row block this shard owns."""
    vlocal = arena_shard.shape[0]
    return jax.lax.axis_index(axis) * vlocal, vlocal


def _masked_partial_reduce(gather_f32, lo, vlocal: int, flat: jax.Array,
                           offsets: jax.Array, axis: str) -> jax.Array:
    """The ownership protocol every sharded sparse path shares: foreign
    rows are gathered as local row 0 and zero-masked, partial bags are
    segment-reduced locally, one psum combines them — only reduced
    (n_bags, D) partials ever cross chips. `gather_f32(local_rows)` loads
    shard rows as f32 (plain take, or dequantize-on-load). One body, so
    the fp and int8 sharded paths can never diverge on the masking edge.
    """
    n = flat.shape[0]
    n_bags = offsets.shape[0] - 1
    seg = ragged_segment_ids(offsets, n)
    rel = flat - lo
    mine = (rel >= 0) & (rel < vlocal) & (seg < n_bags)
    safe = jnp.where(mine, rel, 0)
    rows = jnp.where(mine[..., None], gather_f32(safe), 0)   # (N, D)
    part = jax.ops.segment_sum(rows, jnp.minimum(seg, n_bags - 1),
                               num_segments=n_bags)
    return jax.lax.psum(part, axis)


def _masked_fixed_partial_reduce(gather_f32, lo, vlocal: int,
                                 flat: jax.Array, axis: str, *,
                                 null_row=None) -> jax.Array:
    """Fixed-L sibling of ``_masked_partial_reduce`` — the same ownership
    protocol over (B*T, L) row blocks: foreign rows gathered as local row
    0 and zero-masked, per-bag sum, one psum. One body, so the fp and
    int8 fixed-path shard reduces can never diverge on the masking edge
    either. When ``null_row`` is given, references to that always-zero
    sentinel are masked too (same forward, no gradient leaks into the
    sentinel on the shard that owns it). Returns f32 (B*T, D)."""
    rel = flat - lo
    mine = (rel >= 0) & (rel < vlocal)
    if null_row is not None:
        mine = mine & (flat != null_row)
    safe = jnp.where(mine, rel, 0)
    rows = jnp.where(mine[..., None], gather_f32(safe), 0)
    return jax.lax.psum(rows.sum(axis=1), axis)


def ragged_partial_reduce(arena_shard: jax.Array, flat: jax.Array,
                          offsets: jax.Array, axis: str) -> jax.Array:
    """Shard-local half of a ragged reduce over pre-flattened arena rows.
    Must run inside shard_map (or a vmap with a named axis). Returns f32
    (n_bags, D)."""
    lo, vlocal = shard_row_range(arena_shard, axis)
    return _masked_partial_reduce(
        lambda safe: jnp.take(arena_shard, safe, axis=0)
        .astype(jnp.float32), lo, vlocal, flat, offsets, axis)


def ragged_partial_reduce_q(q_shard: jax.Array, scales_shard: jax.Array,
                            flat: jax.Array, offsets: jax.Array,
                            axis: str) -> jax.Array:
    """`ragged_partial_reduce` over a row-sharded int8 arena: owned rows are
    dequantized locally (rows * per-row scale) before the masked segment
    reduce, so raw int8 rows never cross chips either."""
    lo, vlocal = shard_row_range(q_shard, axis)
    return _masked_partial_reduce(
        lambda safe: jnp.take(q_shard, safe, axis=0).astype(jnp.float32)
        * jnp.take(scales_shard, safe, axis=0),
        lo, vlocal, flat, offsets, axis)


def lookup_ragged_sharded(arena_shard: jax.Array, spec: ArenaSpec,
                          indices: jax.Array, offsets: jax.Array,
                          axis: str) -> jax.Array:
    """DEPRECATED shim: shard-local ragged reduce, now
    ``FpArena(arena_shard).shard_reduce_flat`` (inside shard_map)."""
    from repro.core import embedding_source as es
    _deprecated("lookup_ragged_sharded",
                "ShardedArena(FpArena(arena), mesh) with lookup_bags")
    n_bags = offsets.shape[0] - 1
    flat = flatten_ragged_indices(spec, indices, offsets)
    out = es.FpArena(arena_shard).shard_reduce_flat(spec, flat, offsets,
                                                    axis)
    return out.reshape(n_bags // spec.n_tables, spec.n_tables,
                       spec.dim).astype(arena_shard.dtype)


def mesh_shards(mesh: Optional[jax.sharding.Mesh],
                axis: str = "model") -> int:
    """Number of row shards a (mesh, axis) pair implies (1 = replicated)."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def lookup_ragged_auto(arena: jax.Array, spec: ArenaSpec,
                       indices: jax.Array, offsets: jax.Array, *,
                       max_l: int,
                       mesh: Optional[jax.sharding.Mesh] = None,
                       axis: str = "model") -> jax.Array:
    """DEPRECATED shim: use ``lookup_bags(resolve_source(arena, mesh))``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_ragged_auto",
                "lookup_bags(resolve_source(arena, mesh))")
    return es.lookup_bags(es.resolve_source(arena, mesh, axis), spec,
                          indices, offsets, max_l=max_l)


def lookup_ragged_quantized(q: jax.Array, scales: jax.Array,
                            spec: ArenaSpec, indices: jax.Array,
                            offsets: jax.Array) -> jax.Array:
    """DEPRECATED shim: use ``lookup_bags(QuantizedArena(q, scales), …)``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_ragged_quantized",
                "lookup_bags(QuantizedArena(q, scales), ...)")
    # this shim predates max_l; the stream length is the safe static bound
    return es.lookup_bags(es.QuantizedArena(q, scales), spec, indices,
                          offsets, max_l=int(indices.shape[0]))


def null_indices(spec: ArenaSpec, shape) -> jax.Array:
    """Per-table ids of given (..., T, L) shape that all flatten to the
    null (always-zero) arena row: id (T - t)*rows_per_table for table t.

    Gathering them is a zero-contribution reduction over one hot-in-cache
    row — the zero-cost dummy stream for pipeline tails.
    """
    assert shape[-2] == spec.n_tables, (shape, spec.n_tables)
    ids = (spec.n_tables - jnp.arange(spec.n_tables, dtype=jnp.int32)) \
        * spec.rows_per_table
    return jnp.broadcast_to(ids[:, None], shape)


# ---------------------------------------------------------------------------
# Hot-row cache (beyond-paper: RecNMP-style exploitation of Zipfian skew)
#
# Production embedding traces are heavily skewed: a few thousand rows absorb
# most lookups. The top-K rows by trace frequency are pinned in a small
# replicated "hot" arena (K+1 rows, slot K the zero null slot); cold rows
# stay in the big sharded / quantized arena. A lookup splits into two
# mask-free fused passes — hot slots (misses -> null slot) + cold rows
# (hits -> null row) — and their sum is exactly the uncached result.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HotRowCache:
    hot_rows: jax.Array      # (K+1, D), slot K always zero
    slot_of: jax.Array       # (arena_rows,) int32: slot, or K when cold
    hot_ids: jax.Array       # (K,) int32 pinned arena rows (stats/debug)

    @property
    def k(self) -> int:
        return self.hot_rows.shape[0] - 1


jax.tree_util.register_dataclass(
    HotRowCache, data_fields=("hot_rows", "slot_of", "hot_ids"),
    meta_fields=())


def trace_row_counts(spec: ArenaSpec, indices, offsets=None,
                     rows: Optional[int] = None) -> np.ndarray:
    """Arena-row touch histogram from an access trace (host-side).

    indices: fixed-shape (B, T, L) per-table ids, or — with `offsets` —
    the flat ragged stream (padded tail ignored).
    """
    rows = rows or spec.total_rows
    if offsets is None:
        flat = np.asarray(flatten_indices(spec, jnp.asarray(indices)))
        flat = flat.ravel()
    else:
        idx = np.asarray(indices)
        off = np.asarray(offsets)
        n_valid = int(off[-1])
        seg = np.searchsorted(off[1:], np.arange(n_valid), side="right")
        flat = idx[:n_valid] + (seg % spec.n_tables) * spec.rows_per_table
    return np.bincount(flat, minlength=rows)


def build_hot_cache(arena: jax.Array, spec: ArenaSpec, counts,
                    k: int) -> HotRowCache:
    """Pin the top-k arena rows by trace frequency (host-side build)."""
    counts = np.asarray(counts)[:spec.null_row]     # real rows only
    k = int(min(k, counts.size))
    hot_ids = np.argsort(counts, kind="stable")[::-1][:k].astype(np.int32)
    slot_of = np.full((arena.shape[0],), k, np.int32)
    slot_of[hot_ids] = np.arange(k, dtype=np.int32)
    hot_rows = jnp.concatenate(
        [jnp.take(arena, jnp.asarray(hot_ids), axis=0),
         jnp.zeros((1, arena.shape[1]), arena.dtype)], axis=0)
    return HotRowCache(hot_rows=hot_rows, slot_of=jnp.asarray(slot_of),
                       hot_ids=jnp.asarray(hot_ids))


def cache_split_flat(cache: HotRowCache, null_row: int, flat: jax.Array,
                     offsets: jax.Array, max_l: int):
    """THE hot/cold split over pre-flattened arena row ids — the single
    definition of the exactness-critical protocol (``CachedSource`` and
    the legacy-shaped ``cache_split`` both call it): the hot pass reduces
    cache slots (misses hit the zero null slot), and cold_idx redirects
    cached rows to the arena null row so any cold reduction over it is
    exactly the complement. Returns (hot_sum (n_bags, D) f32,
    cold_idx (N,))."""
    k = cache.hot_rows.shape[0] - 1
    slots = jnp.take(cache.slot_of, flat)
    hot = ops.sparse_lengths_sum(cache.hot_rows, slots, offsets,
                                 max_l=max_l).astype(jnp.float32)
    cold_idx = jnp.where(slots < k, jnp.asarray(null_row, flat.dtype),
                         flat)
    return hot, cold_idx


def cache_split(cache: HotRowCache, spec: ArenaSpec, indices: jax.Array,
                offsets: jax.Array, max_l: int):
    """``cache_split_flat`` over per-table ids (flattens first). Returns
    (hot_sum (n_bags, D) f32, cold_idx (N,), n_bags). Public: benches and
    shard-emulation tests compose custom cold passes from it."""
    n_bags = offsets.shape[0] - 1
    flat = flatten_ragged_indices(spec, indices, offsets)
    hot, cold_idx = cache_split_flat(cache, spec.null_row, flat, offsets,
                                     max_l)
    return hot, cold_idx, n_bags


def lookup_ragged_cached(cache: HotRowCache, arena: jax.Array,
                         spec: ArenaSpec, indices: jax.Array,
                         offsets: jax.Array, *, max_l: int,
                         mesh: Optional[jax.sharding.Mesh] = None,
                         axis: str = "model") -> jax.Array:
    """DEPRECATED shim: use
    ``lookup_bags(CachedSource(cache, resolve_source(arena, mesh)), …)``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_ragged_cached",
                "lookup_bags(CachedSource(cache, <cold source>), ...)")
    src = es.CachedSource(hot=cache,
                          cold=es.resolve_source(arena, mesh, axis))
    return es.lookup_bags(src, spec, indices, offsets, max_l=max_l)


def lookup_ragged_cached_q(cache: HotRowCache, q: jax.Array,
                           scales: jax.Array, spec: ArenaSpec,
                           indices: jax.Array, offsets: jax.Array, *,
                           max_l: int,
                           mesh: Optional[jax.sharding.Mesh] = None,
                           axis: str = "model") -> jax.Array:
    """DEPRECATED shim: use
    ``lookup_bags(CachedSource(cache, QuantizedArena(q, scales)), …)``."""
    from repro.core import embedding_source as es
    _deprecated("lookup_ragged_cached_q",
                "lookup_bags(CachedSource(cache, QuantizedArena(...)), ...)")
    cold = es.QuantizedArena(q=q, scales=scales)
    if mesh_shards(mesh, axis) > 1:
        cold = es.ShardedArena(cold, mesh, axis)
    return es.lookup_bags(es.CachedSource(hot=cache, cold=cold), spec,
                          indices, offsets, max_l=max_l)


def cache_hit_rate(cache: HotRowCache, spec: ArenaSpec, indices: jax.Array,
                   offsets: jax.Array) -> jax.Array:
    """Fraction of (valid) lookups served from the hot arena."""
    k = cache.hot_rows.shape[0] - 1
    flat = flatten_ragged_indices(spec, indices, offsets)
    slots = jnp.take(cache.slot_of, flat)
    n = indices.shape[0]
    valid = jnp.arange(n) < offsets[-1]
    hits = jnp.sum(jnp.where(valid & (slots < k), 1, 0))
    return hits / jnp.maximum(offsets[-1], 1)


def make_zipf_indices(rng: np.random.RandomState, spec: ArenaSpec,
                      batch: int, lookups: int, alpha: float = 1.05) -> np.ndarray:
    """Zipfian sparse-index generator (production access skew), (B, T, L)."""
    raw = rng.zipf(alpha, size=(batch, spec.n_tables, lookups))
    return ((raw - 1) % spec.rows_per_table).astype(np.int32)
