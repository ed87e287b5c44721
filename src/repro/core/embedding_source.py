"""First-class embedding sources: one lookup entry point, swappable backends.

Centaur's core idea is ONE sparse stage with interchangeable
implementations (sparse chiplet vs CPU gather); MP-Rec generalizes that to
runtime selection among embedding-representation paths. This module is
that idea as an API: every way of materializing a reduced embedding bag is
an ``EmbeddingSource`` — a small pytree-registered dataclass — and every
consumer calls exactly one of two entry points:

* ``lookup_bags(source, spec, indices, offsets, *, max_l)`` — the ragged
  production path (paper Fig. 2 SparseLengthsSum), (N,) flat per-table
  ids + (B*T+1,) offsets -> (B, T, D);
* ``lookup_fixed(source, spec, indices)`` — the legacy fixed-L path,
  (B, T, L) -> (B, T, D).

Source taxonomy (composition, not configuration)::

    FpArena(arena)                      full-precision row arena
    QuantizedArena(q, scales)           int8 rows + per-row f32 scale
    ShardedArena(inner, mesh, axis)     row-shard any leaf source's arrays
                                        over a mesh axis (shard_map; one
                                        psum of reduced D-vectors)
    CachedSource(hot, cold)             replicated top-K hot rows + ANY
                                        cold source for the tail
    TableGroupSource(members, specs)    heterogeneous per-table members
                                        (own vocab + dim each), composed
                                        declaratively per table

Composition laws are preserved bit-for-bit vs the pre-API engine:

* hot + cold exactness — ``CachedSource`` reduces cache slots (misses hit
  the zero null slot) and redirects hits to the arena null row before the
  cold pass, so hot_pass + cold_pass == uncached lookup exactly;
* sharded == replicated — ``ShardedArena`` gathers foreign rows as local
  row 0 zero-masked, reduces shard-local partial bags, psums once, and
  rounds the result through the inner source's dtype exactly like the
  replicated kernel does;
* int8 masking — the quantized null row carries a zero scale, so every
  redirect stays inert without masks.

Because sources are pytrees, the *whole source* is a call-time jit
argument: swapping a hot cache, a quantized cold arena, or the full fp
arena on a live engine hits the same compiled executable (same treedef,
same leaf shapes). ``VersionedSource`` wraps any source plus a monotone
version into a self-describing broadcast artifact — the generalization of
the hot-arena artifact to full param publication.

Adding the next source (quantized-hot, two-level cache) is one new
dataclass implementing ``reduce_flat`` — not six new functions.
``TableGroupSource`` closes the per-table-arenas item: every *member* is
itself any of the sources above, so per-table composition (hot-cache only
the skewed tables, int8-quantize only the huge ones) is a value, declared
per table through ``TablePlan``/``SourceSpec.tables``.
"""
from __future__ import annotations

import dataclasses
import io
import json
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse_engine as se
from repro.kernels import ops
from repro.obs.tracing import stage as obs_stage

__all__ = [
    "CachedSource", "EmbeddingSource", "FpArena", "QuantizedArena",
    "ShardedArena", "SourceSpec", "TableGroupSource", "TablePlan",
    "VersionedSource", "describe_source", "fmt_bytes",
    "group_hit_counts", "group_trace_counts", "hot_cache_of",
    "lookup_bags", "lookup_bags_per_table", "lookup_fixed",
    "rebind_arena", "register_meta_type", "register_source",
    "replace_member", "resolve_source", "source_bytes",
    "with_hot_cache",
]

# name -> (cls, data_fields, meta_fields): drives pytree registration,
# artifact (de)serialization, and the API-surface snapshot.
_SOURCE_REGISTRY = {}


def register_source(data_fields: Tuple[str, ...],
                    meta_fields: Tuple[str, ...] = ()):
    """Class decorator: pytree-register a source dataclass and add it to
    the artifact registry. THE extension point — a new source is one
    decorated dataclass implementing ``reduce_flat`` (and optionally the
    fixed / shard-local hooks), nothing else."""
    def deco(cls):
        jax.tree_util.register_dataclass(
            cls, data_fields=list(data_fields),
            meta_fields=list(meta_fields))
        _SOURCE_REGISTRY[cls.__name__] = (cls, tuple(data_fields),
                                          tuple(meta_fields))
        return cls
    return deco


# HotRowCache predates this module but is a serializable component of
# CachedSource artifacts; register it for encode/decode only (it is
# already a pytree).
_SOURCE_REGISTRY["HotRowCache"] = (
    se.HotRowCache, ("hot_rows", "slot_of", "hot_ids"), ())


class EmbeddingSource:
    """Base protocol for embedding sources.

    Subclasses implement ``reduce_flat`` (ragged reduction over
    pre-flattened arena row ids -> f32 partial bags) and ``out_dtype``.
    The production entry points route through ``reduce_dense``: the
    ragged stream is relayouted ONCE into a static (n_bags, max_l) id
    matrix (``se.ragged_dense_ids``) and reduced in a single fused
    gather + per-bag sum — the fused segmented dispatch that keeps every
    flexible path (grouped, cached, sharded) on one pass over the batch.
    ``reduce_dense`` has a default that falls back to ``reduce_flat``
    with uniform offsets, so a new source is still ONE dataclass
    implementing ``reduce_flat``; the built-in sources override it with
    their fused forms. The shard-local hooks (``shard_reduce_flat`` /
    ``shard_reduce_fixed``) are only required of sources that can sit
    inside ``ShardedArena``. ``reduce_bags`` / ``reduce_fixed_ids`` are
    the per-table-id halves of the two entry points; their defaults
    flatten against the uniform arena layout, and only
    ``TableGroupSource`` (whose tables have no shared layout to flatten
    into) overrides them.
    """

    @property
    def out_dtype(self):
        raise NotImplementedError

    def reduce_bags(self, spec: se.ArenaSpec, indices: jax.Array,
                    offsets: jax.Array, *, max_l: int) -> jax.Array:
        """(N,) per-table row ids + (n_bags+1,) offsets -> f32
        (n_bags, D). Default: flatten into the uniform arena layout,
        relayout once, reduce fused. The two halves carry the scopes
        ``relayout`` and ``gather_reduce``, so a device trace splits the
        lookup's time between them."""
        with obs_stage("relayout"):
            flat = se.flatten_ragged_indices(spec, indices, offsets)
            dense = se.ragged_dense_ids(flat, offsets, max_l=max_l,
                                        fill=spec.null_row)
        with obs_stage("gather_reduce"):
            return self.reduce_dense(spec, dense)

    def reduce_fixed_ids(self, spec: se.ArenaSpec,
                         indices: jax.Array) -> jax.Array:
        """(B, T, L) per-table row ids -> f32 (B*T, D)."""
        return self.reduce_fixed(spec, se.flatten_indices(spec, indices))

    def reduce_flat(self, spec: se.ArenaSpec, flat: jax.Array,
                    offsets: jax.Array, *, max_l: int) -> jax.Array:
        """(N,) arena row ids + (n_bags+1,) offsets -> f32 (n_bags, D)."""
        raise NotImplementedError

    def reduce_dense(self, spec: se.ArenaSpec,
                     dense: jax.Array) -> jax.Array:
        """(n_bags, max_l) arena row ids (``se.ragged_dense_ids``
        relayout; short/padded slots point at the zero null row) -> f32
        (n_bags, D). THE fused hook. Default: fall back to the ragged
        reduction with uniform offsets, so reduce_flat-only sources keep
        working unchanged."""
        n_bags, l = dense.shape
        offsets = (jnp.arange(n_bags + 1, dtype=jnp.int32) * l)
        return self.reduce_flat(spec, dense.reshape(-1), offsets, max_l=l)

    def reduce_fixed(self, spec: se.ArenaSpec,
                     flat: jax.Array) -> jax.Array:
        """(B*T, L) arena row ids -> f32 (B*T, D). A fixed-L batch IS
        already a dense id matrix, so this routes straight through the
        fused hook."""
        return self.reduce_dense(spec, flat)

    def shard_reduce_flat(self, spec: se.ArenaSpec, flat: jax.Array,
                          offsets: jax.Array, axis: str) -> jax.Array:
        """Shard-local half of ``reduce_flat`` for use inside shard_map
        (arrays hold this shard's rows); returns psum'd f32 partials."""
        raise NotImplementedError(
            f"{type(self).__name__} cannot be row-sharded; wrap a leaf "
            f"source (FpArena / QuantizedArena) in ShardedArena instead")

    def shard_reduce_fixed(self, spec: se.ArenaSpec, flat: jax.Array,
                           axis: str) -> jax.Array:
        raise NotImplementedError(
            f"{type(self).__name__} cannot be row-sharded; wrap a leaf "
            f"source (FpArena / QuantizedArena) in ShardedArena instead")


def per_device(fn, mesh: Optional[jax.sharding.Mesh]):
    """``fn`` run whole on each device of ``mesh``, over operands that are
    replicated on it. Pallas TPU kernels cannot be partitioned
    automatically, so code that calls them on a multi-device mesh outside
    a ``ShardedArena`` runs under this shard_map. Identity otherwise."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


@register_source(("arena",))
@dataclass(frozen=True)
class FpArena(EmbeddingSource):
    """The plain full-precision row arena — the reference source every
    other composition must agree with."""
    arena: jax.Array                     # (rows, D)

    @property
    def out_dtype(self):
        return self.arena.dtype

    def reduce_flat(self, spec, flat, offsets, *, max_l):
        return ops.sparse_lengths_sum(
            self.arena, flat, offsets, max_l=max_l).astype(jnp.float32)

    def reduce_dense(self, spec, dense):
        return ops.fused_segment_sum(self.arena, dense,
                                     null_row=spec.null_row)

    def reduce_fixed(self, spec, flat):
        # fused EB-Streamer pass (one kernel over all tables)
        return ops.embedding_bag(self.arena, flat).astype(jnp.float32)

    def shard_reduce_flat(self, spec, flat, offsets, axis):
        return se.ragged_partial_reduce(self.arena, flat, offsets, axis)

    def shard_reduce_fixed(self, spec, flat, axis):
        return se.dense_partial_reduce(self.arena, flat, axis,
                                       null_row=spec.null_row)


@register_source(("q", "scales"))
@dataclass(frozen=True)
class QuantizedArena(EmbeddingSource):
    """int8 rows + one f32 scale per row (3.9x capacity); dequantized on
    the fly inside the reduction. The null row's zero scale keeps every
    redirect inert — the int8 masking protocol."""
    q: jax.Array                         # (rows, D) int8
    scales: jax.Array                    # (rows, 1) f32

    @property
    def out_dtype(self):
        return jnp.float32

    @classmethod
    def from_arena(cls, arena: jax.Array) -> "QuantizedArena":
        q, scales = se.quantize_arena(arena)
        return cls(q=q, scales=scales)

    def quantize_rows(self, arena: jax.Array,
                      rows: jax.Array) -> "QuantizedArena":
        """Re-quantize only `rows` from `arena` — the incremental
        maintenance patch. Exact vs a full ``from_arena`` rebuild when
        only `rows` changed (row-wise quantization has no cross-row
        state). Duplicate row ids are harmless (idempotent set)."""
        sub = jnp.take(arena, rows, axis=0).astype(jnp.float32)
        qr, scales = se._rowwise_quantize(sub)   # same rule as from_arena
        return QuantizedArena(q=self.q.at[rows].set(qr),
                              scales=self.scales.at[rows].set(scales))

    def reduce_flat(self, spec, flat, offsets, *, max_l):
        n_bags = offsets.shape[0] - 1
        seg = se.ragged_segment_ids(offsets, flat.shape[0])
        rows = jnp.take(self.q, flat, axis=0).astype(jnp.float32) \
            * jnp.take(self.scales, flat, axis=0)
        return jax.ops.segment_sum(rows, seg, num_segments=n_bags)

    def reduce_dense(self, spec, dense):
        # dequantize-in-the-gather, one per-bag sum, no scatter (the
        # null row's zero scale keeps fill slots inert)
        rows = jnp.take(self.q, dense, axis=0).astype(jnp.float32)
        s = jnp.take(self.scales, dense, axis=0)
        return (rows * s).sum(axis=1)

    def reduce_fixed(self, spec, flat):
        return self.reduce_dense(spec, flat)

    def shard_reduce_flat(self, spec, flat, offsets, axis):
        return se.ragged_partial_reduce_q(self.q, self.scales, flat,
                                          offsets, axis)

    def shard_reduce_fixed(self, spec, flat, axis):
        lo, vlocal = se.shard_row_range(self.q, axis)
        return se._masked_fixed_partial_reduce(
            lambda safe: jnp.take(self.q, safe, axis=0)
            .astype(jnp.float32)
            * jnp.take(self.scales, safe, axis=0), lo, vlocal, flat,
            axis, null_row=spec.null_row)


@register_source(("inner",), ("mesh", "axis"))
@dataclass(frozen=True)
class ShardedArena(EmbeddingSource):
    """Row-shard any leaf source over `axis` of `mesh` (shard_map).

    The ownership protocol every sharded path shares: foreign rows are
    gathered as local row 0 and zero-masked, partial bags are reduced
    shard-locally, one psum combines them — only reduced (n_bags, D)
    partials ever cross chips, never raw rows (Centaur streams reductions
    for the same reason). The psum'd f32 result is rounded through the
    inner source's dtype so sharded and replicated stay bit-comparable on
    low-precision arenas too.
    """
    inner: EmbeddingSource
    mesh: jax.sharding.Mesh
    axis: str = "model"

    @property
    def out_dtype(self):
        return self.inner.out_dtype

    @property
    def n_shards(self) -> int:
        return se.mesh_shards(self.mesh, self.axis)

    def _shard_map(self, local_fn, batch_args, batch_specs, out_spec):
        """shard_map `local_fn(inner_local, *batch_args)` with the inner
        source's leaves row-sharded over `axis` and the given batch /
        output partitioning. Generic over the inner structure, so any
        leaf source gains the sharded composition for free."""
        from jax.sharding import PartitionSpec as P
        leaves, treedef = jax.tree_util.tree_flatten(self.inner)

        def body(*args):
            ls, rest = args[:len(leaves)], args[len(leaves):]
            return local_fn(jax.tree_util.tree_unflatten(treedef, ls),
                            *rest)

        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=tuple(P(self.axis, None) for _ in leaves)
            + tuple(batch_specs),
            out_specs=out_spec, check_vma=False)
        return fn(*leaves, *batch_args)

    def _data_axes(self):
        """The non-row mesh axes: the fixed-path batch partitions over
        them (each data-group reduces only its own samples)."""
        return tuple(a for a in self.mesh.axis_names if a != self.axis)

    def reduce_flat(self, spec, flat, offsets, *, max_l):
        from jax.sharding import PartitionSpec as P
        if self.n_shards == 1:
            return self.inner.reduce_flat(spec, flat, offsets,
                                          max_l=max_l)
        # the ragged stream cannot split over a data axis (offsets are
        # global bag boundaries): batch args stay replicated, one psum
        # of reduced partials over the row axis
        part = self._shard_map(
            lambda src, f, o: src.shard_reduce_flat(spec, f, o,
                                                    self.axis),
            (flat, offsets), (P(None), P(None)), P(None, None))
        # round through the inner dtype exactly like the replicated
        # kernel does, so both partitions stay bit-comparable
        return part.astype(self.inner.out_dtype).astype(jnp.float32)

    def reduce_dense(self, spec, dense):
        from jax.sharding import PartitionSpec as P
        if self.n_shards == 1:
            return self.inner.reduce_dense(spec, dense)
        # the fused sharded cold pass: the gather happens INSIDE
        # shard_map (each shard gathers only the rows it owns, masked,
        # and reduces its partial bags in the same op) — no per-shard
        # ragged partials are ever materialized, one psum of reduced
        # (n_bags, D) vectors crosses chips
        part = self._shard_map(
            lambda src, d: src.shard_reduce_fixed(spec, d, self.axis),
            (dense,), (P(None, None),), P(None, None))
        return part.astype(self.inner.out_dtype).astype(jnp.float32)

    def reduce_fixed(self, spec, flat):
        from jax.sharding import PartitionSpec as P
        if self.n_shards == 1:
            return self.inner.reduce_fixed(spec, flat)
        # fixed-L bags are independent rows of (B*T, L): partition them
        # over the remaining (data) mesh axes so each data-group gathers
        # and reduces only its own samples
        other = self._data_axes()
        batch_spec = P(other if other else None)
        out_spec = P(other if other else None, None)
        part = self._shard_map(
            lambda src, f: src.shard_reduce_fixed(spec, f, self.axis),
            (flat,), (batch_spec,), out_spec)
        return part.astype(self.inner.out_dtype).astype(jnp.float32)


@register_source(("hot", "cold"), ("coherent",))
@dataclass(frozen=True)
class CachedSource(EmbeddingSource):
    """Replicated top-K hot rows + ANY cold source for the tail.

    The shared hot/cold protocol: the hot pass reduces cache slots
    (misses hit the zero null slot), and the cold indices redirect cached
    rows to the arena null row, so any cold reduction over them is
    exactly the complement — hot + cold == uncached, for every cold
    source. Cold may itself be sharded or quantized (or, later, another
    CachedSource — a two-level cache is this dataclass nested).

    ``coherent=True`` is the construction site's declaration that the
    hot copies equal their cold arena rows at serve time (§2 law 1 held
    as an invariant, e.g. a plan built from the live arena). It licenses
    the XLA lowering to serve an FpArena cold straight from the arena —
    one gather, the uncached op histogram — while gradients keep the
    exact hot/cold split and the Pallas kernel keeps the two-table walk.
    Leave it False (the default) when staleness between the hot copies
    and the arena must be observable, i.e. the write-through
    invalidation protocol between an arena update and its hot patch.
    """
    hot: se.HotRowCache
    cold: EmbeddingSource
    coherent: bool = False

    @property
    def out_dtype(self):
        return self.cold.out_dtype

    @property
    def k(self) -> int:
        return self.hot.hot_rows.shape[0] - 1

    def reduce_flat(self, spec, flat, offsets, *, max_l):
        hot, cold_idx = se.cache_split_flat(self.hot, spec.null_row,
                                            flat, offsets, max_l)
        return hot + self.cold.reduce_flat(spec, cold_idx, offsets,
                                           max_l=max_l)

    def reduce_dense(self, spec, dense):
        # ONE pass with the hit test folded into the walk: per position
        # exactly one of hot_rows[slot] (miss -> zero null slot) and
        # cold[cold_id] (hit -> zero null row) is nonzero, so a single
        # merged reduction equals the uncached lookup bit-for-bit —
        # replacing the old hot pass + full cold pass.
        slots = jnp.take(self.hot.slot_of, dense, axis=0)
        cold_ids = jnp.where(slots < self.k,
                             jnp.asarray(spec.null_row, dense.dtype),
                             dense)
        cold = self.cold
        if isinstance(cold, FpArena):
            # dense_ids= opts into the coherence-law lowering (see the
            # class docstring): on XLA the forward collapses to the
            # plain arena reduction, while the backward keeps the exact
            # hot/cold grad split and Pallas keeps the two-table walk.
            return ops.fused_cached_segment_sum(
                self.hot.hot_rows, cold.arena, slots, cold_ids,
                dense_ids=dense if self.coherent else None,
                null_row=spec.null_row)
        if isinstance(cold, QuantizedArena):
            rows = jnp.take(self.hot.hot_rows, slots, axis=0) \
                .astype(jnp.float32) \
                + jnp.take(cold.q, cold_ids, axis=0).astype(jnp.float32) \
                * jnp.take(cold.scales, cold_ids, axis=0)
            return rows.sum(axis=1)
        # sharded (or any other) cold source: fused hot pass + the cold
        # source's own fused pass over the redirected ids
        hot = per_device(
            lambda h, i: ops.fused_segment_sum(h, i, null_row=self.k),
            getattr(cold, "mesh", None))(self.hot.hot_rows, slots)
        return hot + cold.reduce_dense(spec, cold_ids)


@register_source(("members",), ("specs",))
@dataclass(frozen=True)
class TableGroupSource(EmbeddingSource):
    """Heterogeneous per-table embedding sources behind the ONE entry
    point — the workload Centaur characterizes: vocab sizes and access
    skew vary wildly per table, so each table is its own gather-reduce
    stream over its own arena.

    ``members[t]`` is ANY source (``FpArena`` / ``QuantizedArena`` /
    ``CachedSource`` / ``ShardedArena``) over table t's private arena
    ``(vocab_t + 1, dim_t)`` (own trailing null row); ``specs[t]`` is its
    single-table ``ArenaSpec(1, vocab_t, dim_t)``. Per-table composition
    is therefore declarative: hot-cache only the skewed tables, int8 only
    the huge ones (``TablePlan`` / ``SourceSpec.tables``).

    The grouped reduction routes the ONE interleaved (sample, table)
    row-major stream to every member with foreign positions redirected to
    that member's always-zero null row — the same mask-free redirect
    protocol the hot/cold split uses — so each member reduces exactly its
    own bags and contributes exact zeros elsewhere. Outputs are padded to
    ``dmax = max(dim_t)``; table t's slice ``[:, t, :dim_t]`` is
    bit-for-bit the member's own lookup (the composition law pinned by
    ``tests/test_table_group.py``). ``lookup_bags_per_table`` is the
    per-table-stream sibling for callers that keep one stream per table.
    """
    members: Tuple[EmbeddingSource, ...]
    specs: Tuple[se.ArenaSpec, ...]

    @property
    def n_tables(self) -> int:
        return len(self.members)

    @property
    def dmax(self) -> int:
        return max(sp.dim for sp in self.specs)

    @property
    def out_dtype(self):
        return jnp.result_type(*[m.out_dtype for m in self.members])

    @property
    def envelope_spec(self) -> se.ArenaSpec:
        """The uniform ArenaSpec a group serves under: n_tables tables,
        the max vocab, the max dim (only n_tables/dim are consumed by the
        entry points — a group never flattens into a shared arena)."""
        return se.ArenaSpec(len(self.members),
                            max(sp.rows_per_table for sp in self.specs),
                            self.dmax)

    @classmethod
    def from_arenas(cls, arenas: Sequence[jax.Array],
                    specs: Sequence[se.ArenaSpec],
                    mesh: Optional[jax.sharding.Mesh] = None,
                    axis: str = "model") -> "TableGroupSource":
        """The default group for raw per-table arenas: replicated fp
        members, row-sharded when a mesh with a >1 axis is given."""
        assert len(arenas) == len(specs), (len(arenas), len(specs))
        return cls(members=tuple(resolve_source(a, mesh, axis)
                                 for a in arenas),
                   specs=tuple(specs))

    def _position_tables(self, indices, offsets):
        """(table id, validity) per stream position."""
        return se.ragged_position_tables(offsets, indices.shape[0],
                                         len(self.members))

    def reduce_bags(self, spec, indices, offsets, *, max_l):
        t_count = len(self.members)
        assert spec.n_tables == t_count, (spec.n_tables, t_count)
        assert spec.dim == self.dmax, (spec.dim, self.dmax)
        n_bags = offsets.shape[0] - 1
        if n_bags % t_count:
            raise ValueError(
                f"lookup_bags over a TableGroupSource needs the bag "
                f"count to cover whole (sample, table) rows: got "
                f"n_bags={n_bags} bags for t_count={t_count} tables "
                f"(n_bags % t_count == {n_bags % t_count}). Pass "
                f"offsets with B*t_count+1 entries (one bag per sample "
                f"per table, row-major).")
        b = n_bags // t_count
        # ONE relayout of the interleaved stream, then each member
        # reduces only its own (B, max_l) bag slice — total work is N
        # positions, not T*N (the old per-member full-stream walk). -1
        # marks short/padded slots so each table can redirect them to
        # its OWN always-zero null row below.
        with obs_stage("relayout"):
            dense = se.ragged_dense_ids(indices, offsets, max_l=max_l,
                                        fill=-1)
            dense = dense.reshape(b, t_count, max_l)
        cols = []
        with obs_stage("gather_reduce"):
            for t, (m, sp) in enumerate(zip(self.members, self.specs)):
                ids_t = dense[:, t, :]
                ids_t = jnp.where(ids_t >= 0, ids_t,
                                  jnp.asarray(sp.null_row, ids_t.dtype))
                red = m.reduce_dense(sp, ids_t)
                # round through the member dtype exactly like the
                # member's own lookup_bags does, so grouped dispatch
                # stays bit-equal to the per-table loop on low-precision
                # members too
                red = red.astype(m.out_dtype).astype(jnp.float32)
                if sp.dim < spec.dim:
                    red = jnp.pad(red, ((0, 0), (0, spec.dim - sp.dim)))
                cols.append(red)
            return jnp.stack(cols, axis=1).reshape(n_bags, spec.dim)

    def reduce_fixed_ids(self, spec, indices):
        b, t, l = indices.shape
        offsets = jnp.arange(b * t + 1, dtype=jnp.int32) * l
        return self.reduce_bags(spec, indices.reshape(-1), offsets,
                                max_l=l)

    def reduce_flat(self, spec, flat, offsets, *, max_l):
        raise TypeError(
            "TableGroupSource has no shared arena layout to reduce over "
            "— call lookup_bags / lookup_fixed (per-table ids) or "
            "lookup_bags_per_table (per-table streams) instead")

    def reduce_dense(self, spec, dense):
        raise TypeError(
            "TableGroupSource has no shared arena layout to reduce over "
            "— call lookup_bags / lookup_fixed (per-table ids) or "
            "lookup_bags_per_table (per-table streams) instead")


# ---------------------------------------------------------------------------
# The two entry points
# ---------------------------------------------------------------------------

def lookup_bags(source: EmbeddingSource, spec: se.ArenaSpec,
                indices: jax.Array, offsets: jax.Array, *,
                max_l: int) -> jax.Array:
    """THE ragged sparse stage: flat per-table ids + offsets -> (B, T, D).

    Subsumes lookup_ragged / _sharded / _auto / _quantized / _cached /
    _cached_q: the composition lives in the `source` pytree, not in the
    function name. Differentiable w.r.t. the source's fp leaves on every
    backend (``jax.grad`` routes through the kernel custom VJPs). For a
    ``TableGroupSource``, D is the group's ``dmax`` and table t's slice
    ``[..., :dim_t]`` carries its reduced bags (the tail is zero).
    """
    with obs_stage("emb_lookup"):
        n_bags = offsets.shape[0] - 1
        out = source.reduce_bags(spec, indices, offsets, max_l=max_l)
        return out.reshape(n_bags // spec.n_tables, spec.n_tables,
                           spec.dim).astype(source.out_dtype)


def lookup_fixed(source: EmbeddingSource, spec: se.ArenaSpec,
                 indices: jax.Array) -> jax.Array:
    """The legacy fixed-L sparse stage: (B, T, L) ids -> (B, T, D).

    Subsumes lookup / lookup_sharded / lookup_auto / lookup_quantized.
    """
    with obs_stage("emb_lookup"):
        b, t, _ = indices.shape
        out = source.reduce_fixed_ids(spec, indices)
        return out.reshape(b, t, spec.dim).astype(source.out_dtype)


def lookup_bags_per_table(source: TableGroupSource,
                          indices: Sequence[jax.Array],
                          offsets: Sequence[jax.Array], *,
                          max_l) -> jax.Array:
    """Per-table-stream sibling of ``lookup_bags`` for table groups.

    ``indices[t]`` / ``offsets[t]`` are table t's own flat id stream and
    (B+1,) bag boundaries — the layout a feature-log pipeline naturally
    produces, and the one that lets each table carry its own padding
    budget (``max_l`` may be one int or a per-table sequence). Returns
    (B, T, dmax) bit-for-bit equal to ``lookup_bags`` over the
    interleaved stream of the same bags: each member reduces exactly the
    same per-bag id runs in the same order either way.
    """
    assert isinstance(source, TableGroupSource), type(source).__name__
    t_count = len(source.members)
    assert len(indices) == t_count and len(offsets) == t_count, \
        (len(indices), len(offsets), t_count)
    if not isinstance(max_l, (tuple, list)):
        max_l = (max_l,) * t_count
    dmax = source.dmax
    cols = []
    for t, (m, sp) in enumerate(zip(source.members, source.specs)):
        out = lookup_bags(m, sp, indices[t], offsets[t], max_l=max_l[t])
        out = out.reshape(-1, sp.dim).astype(jnp.float32)
        if sp.dim < dmax:
            out = jnp.pad(out, ((0, 0), (0, dmax - sp.dim)))
        cols.append(out)
    return jnp.stack(cols, axis=1).astype(source.out_dtype)


# ---------------------------------------------------------------------------
# Construction helpers
# ---------------------------------------------------------------------------

def resolve_source(arena: jax.Array,
                   mesh: Optional[jax.sharding.Mesh] = None,
                   axis: str = "model") -> EmbeddingSource:
    """The default source for a raw arena: replicated fp, row-sharded
    over `axis` when a mesh with a >1 axis is given (the pre-API
    ``lookup_auto`` behavior as a value instead of a function)."""
    src: EmbeddingSource = FpArena(arena)
    if se.mesh_shards(mesh, axis) > 1:
        src = ShardedArena(src, mesh, axis)
    return src


def hot_cache_of(source) -> Optional[se.HotRowCache]:
    """The hot cache a source serves from, or None (non-cached source)."""
    return source.hot if isinstance(source, CachedSource) else None


def with_hot_cache(source: CachedSource,
                   cache: se.HotRowCache) -> CachedSource:
    """Same cold source, new hot cache — the write-through/rebuild swap."""
    assert isinstance(source, CachedSource), source
    return CachedSource(hot=cache, cold=source.cold,
                        coherent=source.coherent)


def replace_member(source: TableGroupSource, t: int,
                   member: EmbeddingSource) -> TableGroupSource:
    """Same group, one member swapped — the per-table component refresh
    (a new hot cache for one skewed table, a re-quantized cold arena for
    one huge table). Structure-preserving when `member` matches the old
    one's treedef, so pushing the result through
    ``RecEngine.update_source`` never recompiles."""
    members = list(source.members)
    members[t] = member
    return TableGroupSource(members=tuple(members), specs=source.specs)


def rebind_arena(source: EmbeddingSource,
                 arena) -> EmbeddingSource:
    """Return `source` with every fp-arena leaf replaced by `arena`
    (quantized arenas are a frozen *representation* of some arena version
    and are left alone — rebuild them explicitly via ``quantize_rows`` /
    ``from_arena``). For a ``TableGroupSource`` pass the sequence of
    per-table arenas. Used to keep a serving source in lockstep when the
    live params object is swapped."""
    if isinstance(source, TableGroupSource):
        assert len(arena) == len(source.members), \
            (len(arena), len(source.members))
        return TableGroupSource(
            members=tuple(rebind_arena(m, a)
                          for m, a in zip(source.members, arena)),
            specs=source.specs)
    if isinstance(source, FpArena):
        return FpArena(arena)
    if isinstance(source, ShardedArena):
        return ShardedArena(rebind_arena(source.inner, arena),
                            source.mesh, source.axis)
    if isinstance(source, CachedSource):
        return CachedSource(source.hot, rebind_arena(source.cold, arena),
                            coherent=source.coherent)
    if hasattr(source, "_rebind_arena"):
        # extension hook (repro.storage.TieredSource refreshes its fp hot
        # tier; frozen quantized tiers stay put like QuantizedArena does)
        return source._rebind_arena(arena)
    return source


def fmt_bytes(n: int) -> str:
    """Human byte label for describe/stats lines: 512 B, 4.0 KB, 5.1 MB."""
    n = float(n)
    for unit in ("B", "KB", "MB", "GB"):
        if n < 1024 or unit == "GB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GB"


def source_bytes(source) -> int:
    """Total device bytes of a source's array leaves (slot maps, scales
    and all) — the denominator of every capacity-multiplier claim.
    Sources backed by off-device state (host tiers) count only their
    device-resident arrays; see their own accounting for host bytes."""
    if hasattr(source, "device_bytes"):
        return int(source.device_bytes())
    leaves = jax.tree_util.tree_leaves(source)
    return int(sum(getattr(x, "nbytes", 0) for x in leaves))


def describe_source(source, *, multiline: bool = False) -> str:
    """Human/stats label: 'fp', 'int8', 'int4', 'sharded(4,fp)',
    'cached(fp)', 'tiered(host)', 'group[...]'… With ``multiline=True``
    every nested source renders one-per-line (indented tree; groups get
    one line per table with that member's vocab/dim, and every member
    line carries its dtype/tier and device byte size — the REPL view of
    a capacity claim) instead of one unreadable nested line."""
    if multiline:
        return "\n".join(_describe_lines(source, 0))
    if isinstance(source, FpArena):
        return "fp"
    if isinstance(source, QuantizedArena):
        return "int8"
    if isinstance(source, ShardedArena):
        return f"sharded({source.n_shards},{describe_source(source.inner)})"
    if isinstance(source, CachedSource):
        return f"cached({describe_source(source.cold)})"
    if isinstance(source, TableGroupSource):
        inner = ",".join(describe_source(m) for m in source.members)
        return f"group[{inner}]"
    if hasattr(source, "_describe"):
        # the extension hook sources outside this module implement
        # (repro.storage: 'int4', 'host', 'tiered(...)')
        return source._describe()
    return type(source).__name__


def _describe_lines(source, depth: int) -> list:
    pad = "  " * depth
    if isinstance(source, FpArena):
        r, d = source.arena.shape
        return [f"{pad}fp arena ({r}x{d}, {source.arena.dtype}, "
                f"{fmt_bytes(source.arena.nbytes)})"]
    if isinstance(source, QuantizedArena):
        r, d = source.q.shape
        nb = source.q.nbytes + source.scales.nbytes
        return [f"{pad}int8 arena ({r}x{d} + f32 row scales, "
                f"{fmt_bytes(nb)})"]
    if isinstance(source, ShardedArena):
        return [f"{pad}sharded over {source.n_shards} x "
                f"'{source.axis}'"] \
            + _describe_lines(source.inner, depth + 1)
    if isinstance(source, CachedSource):
        nb = source.hot.hot_rows.nbytes + source.hot.slot_of.nbytes \
            + source.hot.hot_ids.nbytes
        return [f"{pad}cached (k={source.k} hot rows, "
                f"{source.hot.hot_rows.dtype}, {fmt_bytes(nb)})"] \
            + _describe_lines(source.cold, depth + 1)
    if isinstance(source, TableGroupSource):
        lines = [f"{pad}group ({len(source.members)} tables, "
                 f"dmax={source.dmax}, "
                 f"{fmt_bytes(source_bytes(source))} on device)"]
        for t, (m, sp) in enumerate(zip(source.members, source.specs)):
            lines.append(f"{pad}  table[{t}] vocab={sp.rows_per_table} "
                         f"dim={sp.dim}")
            lines += _describe_lines(m, depth + 2)
        return lines
    if hasattr(source, "_describe_lines"):
        return source._describe_lines(depth)
    return [f"{pad}{type(source).__name__}"]


# ---------------------------------------------------------------------------
# Group accounting helpers (per-table hit rates / trace histograms)
# ---------------------------------------------------------------------------

def group_hit_counts(source: TableGroupSource, indices: jax.Array,
                     offsets: jax.Array, *, max_l: Optional[int] = None):
    """Per-table (hits, lookups) over one interleaved ragged batch.

    Returns two (T,) int32 arrays; a table whose member serves no hot
    cache reports 0 hits (the consumer maps it to None — membership is
    static structure, not data). Jit-friendly: the member walk happens at
    trace time. With ``max_l`` (the lookup's static bound) the stream is
    relayouted once and each table scans only its own (B, max_l) bag
    slice — the same fused dispatch the lookup itself uses — instead of
    T full-stream walks."""
    t_count = len(source.members)
    if max_l is not None:
        n_bags = offsets.shape[0] - 1
        dense = se.ragged_dense_ids(indices, offsets, max_l=max_l,
                                    fill=-1)
        dense = dense.reshape(n_bags // t_count, t_count, max_l)
        hits, looks = [], []
        for t, m in enumerate(source.members):
            ids_t = dense[:, t, :]
            mine = ids_t >= 0
            looks.append(jnp.sum(mine.astype(jnp.int32)))
            cache = hot_cache_of(m)
            if cache is None:
                hits.append(jnp.zeros((), jnp.int32))
            else:
                slots = jnp.take(cache.slot_of,
                                 jnp.where(mine, ids_t, 0))
                hits.append(jnp.sum((mine & (slots < cache.k))
                                    .astype(jnp.int32)))
        return jnp.stack(hits), jnp.stack(looks)
    table, valid = source._position_tables(indices, offsets)
    hits, looks = [], []
    for t, m in enumerate(source.members):
        mine = valid & (table == t)
        looks.append(jnp.sum(mine.astype(jnp.int32)))
        cache = hot_cache_of(m)
        if cache is None:
            hits.append(jnp.zeros((), jnp.int32))
        else:
            slots = jnp.take(cache.slot_of, jnp.where(mine, indices, 0))
            hits.append(jnp.sum((mine & (slots < cache.k))
                                .astype(jnp.int32)))
    return jnp.stack(hits), jnp.stack(looks)


def group_trace_counts(specs: Sequence[se.ArenaSpec], indices,
                       offsets) -> list:
    """Per-table row-touch histograms from an interleaved ragged trace
    (host-side; the group sibling of ``se.trace_row_counts``). Feeds the
    per-table hot rankings of a group plan."""
    idx = np.asarray(indices)
    off = np.asarray(offsets)
    t_count = len(specs)
    n_valid = int(off[-1])
    seg = np.searchsorted(off[1:], np.arange(n_valid), side="right")
    table = seg % t_count
    return [np.bincount(idx[:n_valid][table == t],
                        minlength=sp.total_rows)
            for t, sp in enumerate(specs)]


@dataclass(frozen=True)
class TablePlan:
    """Per-table slice of a group plan: the table's shape plus its OWN
    composition knobs — hot-cache only the skewed tables (``cache_k``),
    int8-quantize only the huge ones (``quantize``), frequency-tier the
    bigger-than-memory ones (``tiers``, a ``repro.storage.TierPolicy``).
    A tuple of these in ``SourceSpec.tables`` is the declarative form of
    a ``TableGroupSource``."""
    rows: int                            # vocab (real rows, null excluded)
    dim: int
    cache_k: int = 0                     # >0: pin this table's top-K hot
    quantize: bool = False               # int8 this table's (cold) arena
    tiers: Optional[object] = None       # storage.TierPolicy: hot/warm/cold

    def __post_init__(self):
        if self.tiers is not None and (self.cache_k or self.quantize):
            raise ValueError(
                "a tiered table IS its own caching/quantization story — "
                "TierPolicy.hot replaces cache_k and the warm/cold tiers "
                "replace quantize; drop cache_k/quantize on this "
                "TablePlan")

    @property
    def arena_spec(self) -> se.ArenaSpec:
        return se.ArenaSpec(1, self.rows, self.dim)


@dataclass(frozen=True)
class SourceSpec:
    """Declarative serving plan: WHICH source to build, not how.

    Replaces the (path string x cache_k x quantize_cold x mesh) kwarg
    cross-product: a RecEngine (or any consumer) takes one SourceSpec and
    calls ``build(arena, spec, counts)``. String shorthands map 1:1 onto
    the old path names via ``from_path`` ('fixed' | 'ragged' | 'cached'
    | 'sharded'). With ``tables`` set (a tuple of ``TablePlan``) the plan
    is a heterogeneous table group: ``build`` takes the *sequence* of
    per-table arenas (and per-table trace histograms) and composes each
    member independently.
    """
    layout: str = "ragged"               # 'ragged' | 'fixed' batch layout
    cache_k: int = 0                     # >0: pin top-K rows hot
    quantize_cold: bool = False          # int8 cold/uncached arena
    mesh: Optional[jax.sharding.Mesh] = None
    axis: str = "model"
    require_mesh: bool = False           # 'sharded': no silent fallback
    tables: Optional[Tuple[TablePlan, ...]] = None   # heterogeneous group
    tiers: Optional[object] = None       # storage.TierPolicy (single table)

    PATH_NAMES = ("fixed", "ragged", "cached", "sharded")

    def __post_init__(self):
        assert self.layout in ("ragged", "fixed"), self.layout
        if self.require_mesh and se.mesh_shards(self.mesh, self.axis) < 2:
            raise ValueError(
                "require_mesh=True (path 'sharded') needs a mesh with a "
                f">1 {self.axis!r} axis — a misconfigured replica must "
                "not silently fall back to the replicated arena")
        if self.layout == "fixed" and (self.cache_k or self.quantize_cold
                                       or self.tables is not None
                                       or self.tiers is not None):
            raise ValueError(
                "layout='fixed' serves through the legacy fixed-L step "
                "and cannot consume a cached/quantized/grouped/tiered "
                "source — drop cache_k/quantize_cold/tables/tiers or "
                "use the ragged layout")
        if self.tables is not None and (self.cache_k or self.quantize_cold
                                        or self.tiers is not None):
            raise ValueError(
                "a table-group plan carries cache_k/quantize/tiers per "
                "TablePlan — the top-level knobs would silently apply "
                "to no table")
        if self.tiers is not None and (self.cache_k or self.quantize_cold):
            raise ValueError(
                "a tiered plan IS its own caching/quantization story — "
                "drop cache_k/quantize_cold")
        if self.tiers is not None \
                and se.mesh_shards(self.mesh, self.axis) > 1:
            raise ValueError(
                "TieredSource does not row-shard (the staging/slot "
                "protocol is replicated-only for now) — drop the mesh "
                "or the tiers")

    @staticmethod
    def from_path(path: Union[str, "SourceSpec"], *, cache_k: int = 0,
                  quantize_cold: bool = False,
                  mesh: Optional[jax.sharding.Mesh] = None,
                  axis: str = "model") -> "SourceSpec":
        """String shorthand -> plan ('cached' consumes cache_k etc.)."""
        if isinstance(path, SourceSpec):
            return path
        assert path in SourceSpec.PATH_NAMES, \
            (path, SourceSpec.PATH_NAMES)
        if path != "cached":
            # refuse to silently drop cache/int8 configuration — an
            # operator who asked for them must pick the 'cached' path
            # (or pass a full SourceSpec) to get them
            assert not cache_k and not quantize_cold, \
                (f"path {path!r} ignores cache_k/quantize_cold; use "
                 f"path 'cached' or a SourceSpec to configure them")
        if path == "fixed":
            return SourceSpec(layout="fixed", mesh=mesh, axis=axis)
        if path == "ragged":
            return SourceSpec(mesh=mesh, axis=axis)
        if path == "sharded":
            return SourceSpec(mesh=mesh, axis=axis, require_mesh=True)
        assert cache_k > 0, "cached path needs cache_k > 0"
        return SourceSpec(cache_k=cache_k, quantize_cold=quantize_cold,
                          mesh=mesh, axis=axis)

    @property
    def cached(self) -> bool:
        if self.tables is not None:
            return any(tp.cache_k > 0 for tp in self.tables)
        return self.cache_k > 0

    def path_name(self) -> str:
        """The nearest legacy shorthand (for stats/back-compat labels)."""
        if self.tables is not None:
            return "grouped"
        if self.tiers is not None:
            return "tiered"
        if self.layout == "fixed":
            return "fixed"
        if self.cached:
            return "cached"
        if self.require_mesh:
            return "sharded"
        return "ragged"

    def build(self, arena, spec: se.ArenaSpec,
              counts=None) -> EmbeddingSource:
        """Materialize the plan for an arena (counts: trace histogram for
        the hot ranking; uniform when omitted). A table-group plan takes
        the sequence of per-table arenas and the list of per-table
        histograms instead."""
        if self.tables is not None:
            return self._build_group(arena, counts)
        if self.tiers is not None:
            return self.tiers.build_source(arena, spec, counts)
        cold: EmbeddingSource = (QuantizedArena.from_arena(arena)
                                 if self.quantize_cold else FpArena(arena))
        if se.mesh_shards(self.mesh, self.axis) > 1:
            cold = ShardedArena(cold, self.mesh, self.axis)
        if not self.cached:
            return cold
        if counts is None:
            counts = np.ones(spec.total_rows)
        hot = se.build_hot_cache(arena, spec, counts, self.cache_k)
        # the hot cache is built from the live arena right here, so the
        # plan declares coherence — serving gets the fast lowering
        return CachedSource(hot=hot, cold=cold, coherent=True)

    def _build_group(self, arenas, counts=None) -> "TableGroupSource":
        assert len(arenas) == len(self.tables), \
            (len(arenas), len(self.tables))
        if counts is None:
            counts = [None] * len(self.tables)
        sharded = se.mesh_shards(self.mesh, self.axis) > 1
        members, specs = [], []
        for tp, arena, c in zip(self.tables, arenas, counts):
            sp = tp.arena_spec
            if tp.tiers is not None:
                if sharded:
                    raise ValueError(
                        "TieredSource does not row-shard — drop the "
                        "mesh or this table's tiers")
                members.append(tp.tiers.build_source(arena, sp, c))
                specs.append(sp)
                continue
            member: EmbeddingSource = (QuantizedArena.from_arena(arena)
                                       if tp.quantize else FpArena(arena))
            if sharded:
                member = ShardedArena(member, self.mesh, self.axis)
            if tp.cache_k > 0:
                if c is None:
                    c = np.ones(sp.total_rows)
                hot = se.build_hot_cache(arena, sp, c, tp.cache_k)
                member = CachedSource(hot=hot, cold=member, coherent=True)
            members.append(member)
            specs.append(sp)
        return TableGroupSource(members=tuple(members),
                                specs=tuple(specs))


# ---------------------------------------------------------------------------
# Versioned broadcast artifact — any source + a monotone version
# ---------------------------------------------------------------------------

# meta-field dataclass types the artifact codec can round-trip by name;
# extension modules add theirs via register_meta_type (repro.storage
# registers TierPolicy on import)
_META_TYPES = {}


def register_meta_type(cls):
    """Register a (plain, frozen) dataclass so it can appear inside a
    source's meta fields and still round-trip through the artifact
    serializer. Fields are encoded recursively, so registered types may
    nest (TablePlan carries a TierPolicy)."""
    _META_TYPES[cls.__name__] = cls
    return cls


def _encode_meta(v):
    """JSON-encode a meta-field value (plain scalars pass through;
    dataclasses and nested tuples get self-describing wrappers, encoded
    per-field so nested meta dataclasses survive the round trip)."""
    if isinstance(v, se.ArenaSpec):
        return {"__arena_spec__": dataclasses.asdict(v)}
    if isinstance(v, TablePlan):
        return {"__table_plan__": {f.name: _encode_meta(getattr(v, f.name))
                                   for f in dataclasses.fields(v)}}
    if type(v).__name__ in _META_TYPES:
        return {"__meta_dc__": type(v).__name__,
                "fields": {f.name: _encode_meta(getattr(v, f.name))
                           for f in dataclasses.fields(v)}}
    if isinstance(v, (tuple, list)):
        return {"__seq__": [_encode_meta(x) for x in v]}
    return v


def _decode_meta(v):
    if isinstance(v, dict) and "__arena_spec__" in v:
        return se.ArenaSpec(**v["__arena_spec__"])
    if isinstance(v, dict) and "__table_plan__" in v:
        return TablePlan(**{k: _decode_meta(x)
                            for k, x in v["__table_plan__"].items()})
    if isinstance(v, dict) and "__meta_dc__" in v:
        name = v["__meta_dc__"]
        if name not in _META_TYPES:
            import repro.storage  # noqa: F401  (registers its types)
        return _META_TYPES[name](**{k: _decode_meta(x)
                                    for k, x in v["fields"].items()})
    if isinstance(v, dict) and "__seq__" in v:
        return tuple(_decode_meta(x) for x in v["__seq__"])
    return v


def _encode(obj, arrays: dict, counter: list):
    if isinstance(obj, (jax.Array, np.ndarray)):
        key = f"a{counter[0]}"
        counter[0] += 1
        arrays[key] = np.asarray(obj)
        return {"kind": "array", "key": key}
    if isinstance(obj, (tuple, list)):
        # the per-table member tuple of a TableGroupSource (and any
        # future source holding a sequence of sub-sources); lists keep
        # their list-ness so a decoded dense head has the same treedef
        # as the params it replaces (list vs tuple is a treedef change,
        # i.e. a recompile on the serving hot path)
        node = {"kind": "seq",
                "items": [_encode(x, arrays, counter) for x in obj]}
        if isinstance(obj, list):
            node["list"] = True
        return node
    if isinstance(obj, dict):
        # the dense-head payload of a VersionedSource ({"bottom": ...,
        # "top": ..., "proj": ...}) — string-keyed pytrees of arrays
        return {"kind": "dict",
                "items": {k: _encode(v, arrays, counter)
                          for k, v in obj.items()}}
    if obj is None:
        return {"kind": "none"}
    name = type(obj).__name__
    if name not in _SOURCE_REGISTRY:
        raise TypeError(f"cannot serialize {name}: not a registered "
                        f"source type ({sorted(_SOURCE_REGISTRY)})")
    _, data_fields, meta_fields = _SOURCE_REGISTRY[name]
    node = {"kind": "node", "type": name, "fields": {}}
    for f in data_fields:
        node["fields"][f] = _encode(getattr(obj, f), arrays, counter)
    for f in meta_fields:
        v = getattr(obj, f)
        if isinstance(v, jax.sharding.Mesh):
            # meshes are host topology, not state: the consumer rebinds
            # its own at deserialize time
            node["fields"][f] = {"kind": "mesh"}
        elif f in getattr(obj, "__ephemeral_meta__", ()):
            # host-process state (a HostStore's residency bookkeeping):
            # like a mesh, the consumer rebinds its own — the decoded
            # source serves exactly the staged snapshot meanwhile
            node["fields"][f] = {"kind": "ephemeral"}
        else:
            node["fields"][f] = {"kind": "meta",
                                 "value": _encode_meta(v)}
    return node


def _decode(node, z, mesh):
    if node["kind"] == "array":
        return jnp.asarray(z[node["key"]])
    if node["kind"] == "seq":
        items = [_decode(x, z, mesh) for x in node["items"]]
        return items if node.get("list") else tuple(items)
    if node["kind"] == "dict":
        return {k: _decode(v, z, mesh) for k, v in node["items"].items()}
    if node["kind"] == "none":
        return None
    assert node["kind"] == "node", node
    if node["type"] not in _SOURCE_REGISTRY:
        # storage sources register on import; an artifact written by a
        # producer that used them must not require the consumer to have
        # imported the package first
        import repro.storage  # noqa: F401
    cls, data_fields, meta_fields = _SOURCE_REGISTRY[node["type"]]
    kw = {}
    for f in data_fields + meta_fields:
        sub = node["fields"][f]
        if sub["kind"] == "mesh":
            kw[f] = mesh
        elif sub["kind"] == "ephemeral":
            kw[f] = None
        elif sub["kind"] == "meta":
            kw[f] = _decode_meta(sub["value"])
        else:
            kw[f] = _decode(sub, z, mesh)
    if cls is ShardedArena and mesh is None:
        # no mesh on the consumer: serve the inner source replicated
        return kw["inner"]
    return cls(**kw)


@dataclass(frozen=True)
class VersionedSource:
    """Any EmbeddingSource plus the monotone version that produced it —
    the fleet broadcast artifact, generalizing the hot-arena-only
    artifact to quantized cold arenas and full fp arenas (param
    broadcast). ``serialize``/``deserialize`` round-trip through one
    self-describing byte blob; ``apply`` adopts it into an engine
    atomically iff strictly newer (idempotent, order-free delivery).

    ``head`` optionally carries the dense MLP parameters ({"bottom",
    "top", and "proj" when heterogeneous}) alongside the sparse source,
    so a cold remote replica adopts *everything* it serves from one blob
    — no in-process parameter sharing with the trainer at all. The head
    rides the same array codec (dicts/lists keep their exact container
    types, so adopting it is treedef-stable: zero recompiles).
    """
    source: EmbeddingSource
    version: int
    head: Optional[Dict] = None

    MAGIC = b"CSA1"              # Centaur source artifact, format v1

    def serialize(self) -> bytes:
        arrays, counter = {}, [0]
        tree = _encode(self.source, arrays, counter)
        extra = {}
        if self.head is not None:
            head_tree = _encode(dict(self.head), arrays, counter)
            extra["head_structure"] = np.frombuffer(
                json.dumps(head_tree).encode(), np.uint8)
        buf = io.BytesIO()
        np.savez(buf,
                 magic=np.frombuffer(self.MAGIC, np.uint8),
                 version=np.asarray(self.version, np.int64),
                 structure=np.frombuffer(
                     json.dumps(tree).encode(), np.uint8),
                 **extra, **arrays)
        return buf.getvalue()

    @staticmethod
    def deserialize(blob: bytes,
                    mesh: Optional[jax.sharding.Mesh] = None
                    ) -> "VersionedSource":
        """Reconstruct; a recorded ShardedArena rebinds to `mesh`, or
        unwraps to its (replicated) inner source when mesh is None."""
        try:
            with np.load(io.BytesIO(blob)) as z:
                if z["magic"].tobytes() != VersionedSource.MAGIC:
                    raise ValueError("bad magic")
                tree = json.loads(z["structure"].tobytes().decode())
                source = _decode(tree, z, mesh)
                head = None
                if "head_structure" in z:
                    head_tree = json.loads(
                        z["head_structure"].tobytes().decode())
                    head = _decode(head_tree, z, mesh)
                return VersionedSource(source=source,
                                       version=int(z["version"]),
                                       head=head)
        except Exception as e:
            raise ValueError(
                f"not a versioned-source artifact: {e}") from e

    def apply(self, engine) -> bool:
        """Adopt into a RecEngine iff strictly newer; same-or-older
        artifacts are absorbed (reordered transport is safe). A carried
        dense head lands *before* the source swap (params first, then
        source — the setter rebinds the old source's arena leaves to the
        unchanged sparse params, so nothing tears), making the pair
        (dense head, sparse source) one atomic version adoption."""
        if engine.source_version >= self.version:
            return False
        if self.head is not None:
            engine.params = {**engine.params, **self.head}
        engine.update_source(self.source, version=self.version)
        return True
