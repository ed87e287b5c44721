"""Host-resident cold tier: rows that never live in device memory.

Centaur's sparse chiplet serves gathers from *capacity* memory while the
dense chiplet computes — the point is that cold embedding rows should
occupy cheap, large storage and cross to the accelerator only when a
batch actually touches them. ``HostStore`` is that tier: the cold rows
stay as one fp32 numpy block on the host, and a small bounded **staging
arena** on device receives exactly the rows the next batches need, via
``jax.device_put`` transfers that overlap the current batch's compute.

The contract with the jitted serve path:

* the device footprint is FIXED — ``staging`` is ``(S+1, D)`` with slot S
  the always-zero null slot, ``slot_of`` maps every compact cold index to
  its staging slot (or S when not resident). Staging updates are scatter
  writes at the same shapes, so the serve executable never recompiles and
  residency changes are pure data.
* ``stage(arena_ids)`` is the synchronous-in-program-order residency
  guarantee the engine calls per batch *before* dispatch: after it
  returns, every cold row the batch touches has a staging slot and a
  pending (async) transfer — XLA's data dependency, not a host sync,
  orders the copy before the gather. A row already resident counts as a
  **hit**; a row staged on demand counts as a **miss**. The accounting
  invariant ``hits + misses == cold row touches`` (unique per batch) is
  asserted by ``bench_paper --smoke``.
* ``prefetch(arena_ids)`` stages *ahead* (the next batches' rows, peeked
  from the admission queue) without touching the hit/miss counters — it
  is how misses become hits. Rows pinned by the current batch are never
  evicted by a prefetch.

Exactness: staged rows are bit-exact fp32 copies of the host block (no
re-quantization on the way in), so a cold row served through the staging
arena equals the fp arena row exactly — the hot/cold composition law
extends to the host tier unchanged.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.kernels import ops

__all__ = ["HostStore", "HostTier"]


@functools.cache
def _explicit_put() -> bool:
    """Issue host->device copies eagerly (device_put futures) only when a
    real accelerator is attached; the CPU backend's jit argument conversion
    is the same copy without the extra Python hop. Decided at first use, so
    importing this module never starts a backend."""
    return jax.default_backend() != "cpu"


@functools.partial(jax.jit, static_argnames=())
def _apply_stage(staging, slot_of, rows, slots, ids, evicted):
    """One fixed-shape staging scatter: evict, remap, write.

    rows (M, D) are the freshly transferred host rows for compact cold
    ids ``ids`` landing in staging ``slots``; ``evicted`` are the compact
    ids losing their slots. Padding protocol (chunks are fixed-size M so
    this never recompiles): pad ids/evicted with the compact NULL id
    (whose slot_of entry is the null slot anyway) and slots with the null
    slot (whose staging row is zero and the pad rows are zero) — every
    pad write rewrites an invariant value. NO buffer donation: in-flight
    dispatched batches hold the previous staging arrays, and immutability
    is exactly what makes asynchronous staging safe.
    """
    null_slot = staging.shape[0] - 1
    slot_of = slot_of.at[evicted].set(null_slot)
    slot_of = slot_of.at[ids].set(slots)
    staging = staging.at[slots].set(rows)
    return staging, slot_of


@es.register_source(("staging", "slot_of"), ("store",))
@dataclass(frozen=True)
class HostTier(es.EmbeddingSource):
    """The device-visible face of a ``HostStore``: the bounded staging
    arena plus the residency map, as an ``EmbeddingSource`` over compact
    cold ids (0..C-1 with C the compact null id).

    ``store`` is *ephemeral* meta (host state, like a Mesh): it keeps the
    treedef identity-stable across staging refreshes in-process, is
    dropped by the artifact serializer, and a deserialized HostTier
    (store=None) still serves exactly its staged snapshot.
    """
    staging: jax.Array                   # (S+1, D) f32, slot S zero
    slot_of: jax.Array                   # (C+1,) int32 -> slot or S
    store: Optional["HostStore"] = None

    __ephemeral_meta__ = ("store",)

    @property
    def out_dtype(self):
        return jnp.float32

    @property
    def staging_rows(self) -> int:
        return self.staging.shape[0] - 1

    def reduce_dense(self, spec, dense):
        # residency indirection then the plain fused reduce: non-resident
        # and null ids read the zero null slot — with the engine's
        # ``stage()`` residency guarantee, every *touched* cold row is
        # resident, so "non-resident" only ever describes fill slots.
        slots = jnp.take(self.slot_of, dense, axis=0)
        return ops.fused_segment_sum(self.staging, slots,
                                     null_row=self.staging_rows)

    def reduce_flat(self, spec, flat, offsets, *, max_l):
        n_bags = offsets.shape[0] - 1
        seg = se.ragged_segment_ids(offsets, flat.shape[0])
        rows = jnp.take(self.staging, jnp.take(self.slot_of, flat),
                        axis=0).astype(jnp.float32)
        return jax.ops.segment_sum(rows, seg, num_segments=n_bags)

    def _describe(self) -> str:
        return "host"

    def _describe_lines(self, depth: int) -> list:
        pad = "  " * depth
        s, d = self.staging.shape
        total = self.store.host_rows.shape[0] if self.store is not None \
            else "?"
        return [f"{pad}host tier ({total} rows on host; staging "
                f"{s - 1}x{d} f32, {es.fmt_bytes(self.device_bytes())} "
                f"on device)"]

    def device_bytes(self) -> int:
        return int(self.staging.nbytes + self.slot_of.nbytes)

    def host_bytes(self) -> int:
        return int(self.store.host_rows.nbytes) \
            if self.store is not None else 0


class HostStore:
    """Host-side owner of a cold-row block + its staging residency state.

    Identity-stable across staging refreshes (it sits in ``HostTier``'s
    meta fields, which participate in treedef equality) — the engine
    carries ONE store per tier for the life of the source and refreshes
    only the ``HostTier`` array leaves around it.
    """

    def __init__(self, host_rows: np.ndarray, *, staging_rows: int,
                 compact_of: Optional[np.ndarray] = None,
                 max_stage_per_batch: int = 64,
                 telemetry: Optional[obs.Telemetry] = None):
        host_rows = np.ascontiguousarray(host_rows, np.float32)
        c, d = host_rows.shape
        assert staging_rows >= 1, staging_rows
        self.host_rows = host_rows           # (C, D) fp32, compact ids
        self.n_cold = c
        self.null_id = c                     # compact null id
        # arena row id -> compact cold id (null_id for non-cold rows);
        # host-side numpy, zero device footprint. Identity when the store
        # is used standalone over a whole arena.
        self.compact_of = (np.asarray(compact_of, np.int64)
                           if compact_of is not None
                           else np.arange(c, dtype=np.int64))
        self.staging_rows = staging_rows
        self.max_stage = max(1, int(max_stage_per_batch))
        self.bind_telemetry(telemetry if telemetry is not None
                            else obs.Telemetry.disabled())
        # live device state (HostTier snapshots these leaves)
        self.staging = jnp.zeros((staging_rows + 1, d), jnp.float32)
        self.slot_of = jnp.full((c + 1,), staging_rows, jnp.int32)
        # residency bookkeeping, all vectorized numpy (this runs on the
        # serve hot path every batch — per-id Python loops would cost
        # more than the transfers they schedule): a host mirror of the
        # slot map, an LRU stamp per compact id, the pin mask of the
        # batch currently in flight, and the free-slot stack
        self._slot_np = np.full(c + 1, staging_rows, np.int32)
        self._stamp = np.zeros(c + 1, np.int64)
        # pin-by-epoch: a row is pinned iff its entry equals the current
        # pin epoch — re-pinning a new working set is one counter bump,
        # not a (C,) memset on the serve hot path
        self._pin_epoch = np.zeros(c + 1, np.int64)
        self._epoch = 0
        # slot -> resident compact id (null_id when free): the eviction
        # planner scans S slots for LRU candidates, not C compact ids
        self._owner = np.full(staging_rows, c, np.int32)
        self._free = np.arange(staging_rows - 1, -1, -1, np.int32)
        self._n_free = staging_rows
        self._clock = 0
        self.hits = 0
        self.misses = 0

    # The store rides in HostTier's *meta* fields, so it participates in
    # pytree-structure comparison and jit signature hashing. The jitted
    # serve path never reads the store — only the snapshot array leaves —
    # so two stores with the same structural signature are interchangeable
    # for compilation purposes. Identity equality here would make a
    # trainer-published source structurally different from the engine's
    # own and force a recompile on every sync.
    def _signature(self) -> tuple:
        return (self.host_rows.shape, self.staging_rows)

    def __eq__(self, other) -> bool:
        return isinstance(other, HostStore) \
            and self._signature() == other._signature()

    def __hash__(self) -> int:
        return hash((HostStore, self._signature()))

    def bind_telemetry(self, telemetry: obs.Telemetry) -> None:
        """Adopt a consumer's telemetry bundle (the engine rebinds the
        stores it discovers in its source; registration is idempotent)."""
        self.telemetry = telemetry
        reg = telemetry.registry
        self._c_hit = reg.counter(
            "rec_prefetch_hit",
            "cold rows already staged when their batch arrived")
        self._c_miss = reg.counter(
            "rec_prefetch_miss",
            "cold rows staged on demand at batch-stage time")

    def retarget(self, host_rows: np.ndarray,
                 compact_of: np.ndarray) -> None:
        """Adopt a new cold partition in place (tier migration): fresh
        rows and arena->compact mapping, residency reset, SAME object
        identity and array shapes — the treedef of any ``HostTier``
        snapshotted from this store is unchanged, so republication after
        a migration still hits the compiled serve path. Requires the
        partition sizes to match (fixed H/W/C is the structure-stability
        contract of ``TierPolicy``)."""
        host_rows = np.ascontiguousarray(host_rows, np.float32)
        assert host_rows.shape == self.host_rows.shape, \
            (host_rows.shape, self.host_rows.shape)
        assert compact_of.shape == self.compact_of.shape, \
            (compact_of.shape, self.compact_of.shape)
        self.host_rows = host_rows
        self.compact_of = np.asarray(compact_of, np.int64)
        self.staging = jnp.zeros_like(self.staging)
        self.slot_of = jnp.full_like(self.slot_of, self.staging_rows)
        self._slot_np[:] = self.staging_rows
        self._stamp[:] = 0
        self._pin_epoch[:] = 0
        self._epoch = 0
        self._owner[:] = self.null_id
        self._free = np.arange(self.staging_rows - 1, -1, -1, np.int32)
        self._n_free = self.staging_rows
        self._clock = 0

    # -- residency ---------------------------------------------------------

    def tier(self) -> HostTier:
        """The current device-visible snapshot of this store."""
        return HostTier(staging=self.staging, slot_of=self.slot_of,
                        store=self)

    def _unique_cold(self, arena_ids) -> np.ndarray:
        ids = np.asarray(arena_ids, np.int64).reshape(-1)
        comp = self.compact_of[ids]
        return np.unique(comp[comp < self.n_cold])

    def cold_ids_of(self, arena_ids) -> np.ndarray:
        """Raw arena row ids -> this store's unique compact cold ids (the
        form ``stage``/``prefetch`` consume). Exposed so a caller staging
        ahead can compute a future batch's cold set once and replay it
        when the batch arrives."""
        return self._unique_cold(arena_ids)

    def stage_arena(self, arena_ids) -> tuple:
        """Per-batch entry point over raw *arena* row ids: filter to this
        store's cold rows, uniquify, guarantee residency."""
        return self.stage(self._unique_cold(arena_ids))

    def prefetch_arena(self, arena_ids) -> int:
        """Prefetch entry point over raw arena row ids."""
        return self.prefetch(self._unique_cold(arena_ids))

    def stage_arena_with_prefetch(self, arena_ids, next_arena_ids) -> tuple:
        """Residency guarantee for the in-flight batch AND best-effort
        prefetch of the next batch, as ONE flush: a single transfer +
        scatter per step instead of two — the fixed per-flush costs
        (pad buffer, ``device_put`` issue, scatter dispatch) are the
        serve hot path's dominant staging expense once the hit rate is
        high. Accounting covers only the in-flight batch."""
        return self.stage(self._unique_cold(arena_ids),
                          ahead=self._unique_cold(next_arena_ids))

    def stage(self, comp_ids: np.ndarray,
              ahead: Optional[np.ndarray] = None) -> tuple:
        """Residency guarantee for one batch's unique compact cold ids.

        Returns (hits, misses) for this batch and re-pins the working
        set; call ``tier()`` (or let the engine refresh its source) to
        pick up the new leaves. ``ahead`` optionally rides best-effort
        prefetch ids (the NEXT batch's) into the same flush, uncounted.
        """
        comp_ids = np.unique(np.asarray(comp_ids, np.int64).reshape(-1))
        resident = self._slot_np[comp_ids] < self.staging_rows
        hits = int(resident.sum())
        need = comp_ids[~resident]
        self._clock += 1
        self._stamp[comp_ids] = self._clock
        # re-pin the new working set (the rows the in-flight batch reads;
        # a prefetch must never evict them from under the dispatch)
        self._epoch += 1
        self._pin_epoch[comp_ids] = self._epoch
        want = need
        if ahead is not None and len(ahead):
            self._clock += 1
            self._stamp[ahead] = self._clock
            amiss = ahead[self._slot_np[ahead] == self.staging_rows]
            if len(amiss):
                # one plan for batch + lookahead: needs first, so when
                # the arena can't fit everything the truncation drops
                # the best-effort tail, never the residency guarantee
                want = np.concatenate(
                    (need, np.setdiff1d(amiss, need, assume_unique=True)))
        self._flush(*self._plan(want, min_required=len(need)))
        self.hits += hits
        self.misses += len(need)
        if self.telemetry.enabled:
            if hits:
                self._c_hit.inc(hits)
            if len(need):
                self._c_miss.inc(len(need))
        return hits, len(need)

    def prefetch(self, comp_ids: np.ndarray) -> int:
        """Stage ahead without touching the hit/miss accounting; returns
        the number of rows actually transferred."""
        comp_ids = np.unique(np.asarray(comp_ids, np.int64).reshape(-1))
        self._clock += 1
        self._stamp[comp_ids] = self._clock
        miss = self._slot_np[comp_ids] == self.staging_rows
        return self._assign(comp_ids[miss], best_effort=True)

    def _assign(self, need: np.ndarray, best_effort: bool) -> int:
        """Plan + flush in one call (the standalone stage/prefetch
        paths)."""
        plan = self._plan(need, min_required=0 if best_effort
                          else len(need))
        self._flush(*plan)
        return len(plan[0])

    def _plan(self, need: np.ndarray, *, min_required: int) -> tuple:
        """Assign slots (free first, then LRU-evict unpinned); returns
        the transfer plan ``(ids, slots, victims)`` for ``_flush``.
        The first ``min_required`` ids are the residency guarantee — if
        they can't all get slots the batch's unique cold rows exceed the
        arena, a plan error, not a runtime to paper over; anything past
        them is best-effort lookahead, truncated when nothing more is
        evictable."""
        none = (np.zeros(0, np.int64), np.zeros(0, np.int32),
                np.zeros(0, np.int64))
        k = len(need)
        if k == 0:
            return none
        take = min(k, self._n_free)
        victims = np.empty(0, np.int64)
        if k > take:
            m = k - take
            res = self._owner[self._owner != self.null_id]
            cand = res[self._pin_epoch[res] != self._epoch]
            if len(cand) < m:
                if take + len(cand) < min_required:
                    raise ValueError(
                        f"staging arena too small: batch needs more than "
                        f"{self.staging_rows} unique cold rows "
                        f"(TierPolicy.staging_rows)")
                m = len(cand)
                k = take + m
                need = need[:k]
                if k == 0:
                    return none
            if m:
                sel = (np.argpartition(self._stamp[cand], m - 1)[:m]
                       if m < len(cand) else np.arange(len(cand)))
                victims = cand[sel]
        new_slots = np.empty(k, np.int32)
        if take:
            new_slots[:take] = self._free[self._n_free - take:self._n_free]
            self._n_free -= take
        if len(victims):
            new_slots[take:k] = self._slot_np[victims]
            self._slot_np[victims] = self.staging_rows
        self._slot_np[need] = new_slots
        self._owner[new_slots] = need
        return need, new_slots, victims

    @property
    def _chunk_sizes(self) -> tuple:
        """Fixed-shape flush chunk ladder. At a healthy hit rate a batch
        transfers a handful of rows; padding them to ``max_stage`` makes
        the pad buffer + transfer the dominant staging cost. A small
        pre-compiled chunk serves the steady state, ``max_stage`` serves
        bursts — both warmed by ``warm_compile`` so neither ever jits on
        the serve path."""
        sizes = []
        c = 32
        while c < self.max_stage:
            sizes.append(c)
            c *= 2
        return tuple(sizes) + (self.max_stage,)

    def warm_compile(self) -> None:
        """Compile the staging scatter at every flush chunk size, off the
        serve clock. All-pad flushes: every write rewrites an invariant
        value (null id -> null slot, zero rows into the null slot), so
        residency is untouched."""
        for m in self._chunk_sizes:
            rows = jax.device_put(
                np.zeros((m, self.host_rows.shape[1]), np.float32))
            pad_i = np.full(m, self.null_id, np.int32)
            pad_s = np.full(m, self.staging_rows, np.int32)
            self.staging, self.slot_of = _apply_stage(
                self.staging, self.slot_of, rows, pad_s, pad_i, pad_i)

    def _flush(self, ids, slots, evicted):
        n = max(len(ids), len(evicted))
        if n == 0:
            return
        m = next((c for c in self._chunk_sizes if n <= c),
                 self.max_stage)
        for i in range(0, n, m):
            ids_c = ids[i:i + m]
            slots_c = slots[i:i + m]
            ev_c = evicted[i:i + m]
            # fixed-shape padding (see _apply_stage): pad writes rewrite
            # invariant values, so chunking never recompiles
            rows_np = np.zeros((m, self.host_rows.shape[1]), np.float32)
            if len(ids_c):
                rows_np[:len(ids_c)] = self.host_rows[ids_c]
            ids_a = np.full(m, self.null_id, np.int32)
            ids_a[:len(ids_c)] = ids_c
            slots_a = np.full(m, self.staging_rows, np.int32)
            slots_a[:len(slots_c)] = slots_c
            ev_a = np.full(m, self.null_id, np.int32)
            ev_a[:len(ev_c)] = ev_c
            # the async transfer: on an accelerator, device_put returns
            # immediately with the H2D copy in flight, the scatter
            # consumes the future, and the serving gather orders itself
            # after it by data dependency — no host sync anywhere. On the
            # CPU backend the jit argument conversion IS that (zero-copy)
            # transfer, and an explicit device_put would only add a
            # Python round-trip to the same buffer.
            rows_dev = jax.device_put(rows_np) if _explicit_put() \
                else rows_np
            self.staging, self.slot_of = _apply_stage(
                self.staging, self.slot_of, rows_dev,
                slots_a, ids_a, ev_a)

    # -- accounting --------------------------------------------------------

    @property
    def touches(self) -> int:
        """Unique cold rows demanded by batches so far (the invariant:
        touches == hits + misses, asserted by the bench smoke)."""
        return self.hits + self.misses

    def hit_rate(self) -> float:
        t = self.touches
        return self.hits / t if t else 1.0

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "touches": self.touches, "hit_rate": self.hit_rate(),
                "resident": int(self.staging_rows - self._n_free),
                "staging_rows": self.staging_rows,
                "host_rows": self.n_cold,
                "host_bytes": int(self.host_rows.nbytes)}
