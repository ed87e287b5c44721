"""Online trainer: ragged training loop + live hot-cache refresh.

See the package docstring (repro.training) for the versioned swap protocol
and its exactness invariant. The trainer owns three pieces of state:

* model/optimizer state, advanced by ``dlrm.make_train_step_ragged``;
* a host-side exponentially *decayed* row-frequency histogram of the live
  index stream (``hist = decay * hist + batch_counts`` each step) — the
  online replacement for the offline trace histogram, so the ranking
  follows drift instead of averaging over all of history;
* the current ``VersionedHotCache``, rebuilt every ``refresh_every`` steps
  and write-through-patched after every optimizer step in between.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import DLRMConfig
from repro.core import dlrm
from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.core.embedding_source import VersionedSource


def _dense_head(params: Dict) -> Optional[Dict]:
    """The dense-stage parameters a broadcast artifact ships alongside
    the sparse source: the bottom/top MLPs plus the per-table projections
    of a heterogeneous model. Container types are preserved verbatim by
    the artifact codec, so adopting the decoded head keeps the params
    treedef (no recompile)."""
    head = {k: params[k] for k in ("bottom", "top", "proj") if k in params}
    return head or None


@dataclass(frozen=True)
class OnlineCacheConfig:
    k: int                       # hot rows pinned per rebuild
    refresh_every: int = 50      # steps between re-rank + rebuild
    decay: float = 0.98          # per-step histogram decay
    quantize_cold: bool = False  # maintain an int8 cold arena alongside
    #                              the fp one, re-quantizing only the rows
    #                              touched since the last rebuild
    tiers: Optional[object] = None   # storage.TierPolicy: maintain a
    #                              frequency-tiered serving source instead
    #                              of the hot-cache/cold-arena pair; the
    #                              rebuild cadence becomes the tier-
    #                              migration cadence (k is ignored)

    def __post_init__(self):
        if self.tiers is not None and (self.k or self.quantize_cold):
            raise ValueError(
                "a tiered maintenance plan replaces the hot cache and "
                "the int8 mirror (TierPolicy.hot is the hot set; the "
                "warm/cold tiers are the quantized story) — set k=0 and "
                "quantize_cold=False")


@dataclass(frozen=True)
class VersionedHotCache:
    """A hot cache plus the monotone version of the rebuild that made it.

    Also the fleet *broadcast artifact*: ``serialize`` flattens the pair
    into one self-describing byte blob the trainer can put on any
    transport (object store, pub/sub, NFS), ``deserialize`` reconstructs
    it on a serving host, and ``apply`` adopts it into a ``RecEngine``
    atomically — the engine either serves its old version or the new one,
    never a torn mix, and stale (lower-version) artifacts are rejected at
    the engine boundary, so out-of-order delivery is safe.
    """
    cache: se.HotRowCache
    version: int

    MAGIC = b"CHC1"          # Centaur hot-cache artifact, format v1

    def serialize(self) -> bytes:
        """Flatten (cache, version) into a byte blob (npz container)."""
        import io

        buf = io.BytesIO()
        np.savez(buf,
                 magic=np.frombuffer(self.MAGIC, np.uint8),
                 version=np.asarray(self.version, np.int64),
                 hot_rows=np.asarray(self.cache.hot_rows),
                 slot_of=np.asarray(self.cache.slot_of),
                 hot_ids=np.asarray(self.cache.hot_ids))
        return buf.getvalue()

    @staticmethod
    def deserialize(blob: bytes) -> "VersionedHotCache":
        import io

        try:
            with np.load(io.BytesIO(blob)) as z:
                if z["magic"].tobytes() != VersionedHotCache.MAGIC:
                    raise ValueError("bad magic")
                cache = se.HotRowCache(
                    hot_rows=jnp.asarray(z["hot_rows"]),
                    slot_of=jnp.asarray(z["slot_of"]),
                    hot_ids=jnp.asarray(z["hot_ids"]))
                return VersionedHotCache(cache=cache,
                                         version=int(z["version"]))
        except Exception as e:
            raise ValueError(
                f"not a hot-cache broadcast artifact: {e}") from e

    def apply(self, engine) -> bool:
        """Adopt this artifact into a RecEngine iff it is strictly newer.

        Returns True when the engine swapped. Same-version re-delivery is
        a no-op (idempotent broadcast); an older version raises inside
        ``update_cache`` only on a direct call — here it is absorbed, so
        replicas can consume a reordered stream without try/except at
        every site.
        """
        if engine.cache_version >= self.version:
            return False
        engine.update_cache(self.cache, version=self.version)
        return True


def _patch_tiered_hot(tiered, arena: jax.Array, null_row: int,
                      rows: jax.Array):
    """Write-through invalidation for a TieredSource's fp hot tier: the
    rows just trained refresh their hot copies; warm/cold rows route to
    the hot null slot, whose source is forced to the always-zero null
    arena row — the same only-zeros-can-write-the-null-slot invariant
    ``_patch_hot_rows`` keeps."""
    import dataclasses as _dc
    h = tiered.hot_rows.shape[0] - 1
    ts = jnp.take(tiered.tier_slot, rows)
    slots = jnp.where(ts < h, ts, h)
    src = jnp.where(ts < h, rows, null_row)
    fresh = jnp.take(arena, src, axis=0).astype(tiered.hot_rows.dtype)
    return _dc.replace(tiered,
                       hot_rows=tiered.hot_rows.at[slots].set(fresh))


def _patch_hot_rows(cache: se.HotRowCache, arena: jax.Array,
                    null_row: int, rows: jax.Array) -> se.HotRowCache:
    """Write-through invalidation: refresh the hot copies of `rows`.

    Rows that are not pinned map to the null slot, whose *source* is forced
    to the always-zero null arena row — the null slot can only ever be
    rewritten with zeros, so the mask-free hot pass stays exact.
    """
    k = cache.hot_rows.shape[0] - 1
    slots = jnp.take(cache.slot_of, rows)
    src = jnp.where(slots < k, rows, null_row)
    fresh = jnp.take(arena, src, axis=0).astype(cache.hot_rows.dtype)
    return se.HotRowCache(hot_rows=cache.hot_rows.at[slots].set(fresh),
                          slot_of=cache.slot_of, hot_ids=cache.hot_ids)


class OnlineTrainer:
    """Consume ragged batches; keep the serving hot cache live and exact."""

    def __init__(self, cfg: DLRMConfig, params: Dict, *, max_l: int,
                 lr: float = 1e-3, sparse: bool = True,
                 cache_cfg: Optional[OnlineCacheConfig] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 telemetry: Optional[obs.Telemetry] = None):
        self.telemetry = telemetry if telemetry is not None \
            else obs.Telemetry()
        reg = self.telemetry.registry
        self._g_loss = reg.gauge("train_loss", "last optimizer-step loss")
        self._g_version = reg.gauge("train_cache_version",
                                    "last published rebuild version")
        self._g_hot_k = reg.gauge("train_rebuild_hot_k",
                                  "hot rows pinned by the last rebuild")
        self._g_requant = reg.gauge(
            "train_requant_rows",
            "rows re-quantized by the last incremental refresh")
        self._c_steps = reg.counter("train_steps_total",
                                    "optimizer steps taken")
        self._c_rebuilds = reg.counter("train_rebuilds_total",
                                       "hot-cache rebuilds")
        self.cfg = cfg
        self.spec = dlrm.arena_spec(cfg)
        self.params = params
        self.max_l = max_l
        self.cache_cfg = cache_cfg
        self.mesh = mesh
        opt, step = dlrm.make_train_step_ragged(cfg, max_l=max_l, lr=lr,
                                                sparse=sparse, mesh=mesh)
        self.opt_state = opt.init(params)
        # donate opt_state so its (V, 1) accumulator updates in place;
        # params CANNOT be donated — sync_engine publishes the live arrays
        # to serving engines by reference, and donation would free them
        self._step = jax.jit(step, donate_argnums=(1,))
        self._patch = jax.jit(_patch_hot_rows, static_argnums=(2,))
        self.hist = np.zeros(self.spec.total_rows, np.float64)
        self.steps = 0
        self.version = 0
        self.cache: Optional[se.HotRowCache] = None
        self.losses: list = []
        # incremental quantized-cold maintenance (ROADMAP): keep an int8
        # mirror of the arena and the set of rows dirtied since the last
        # requant, so each rebuild patches O(touched) rows instead of
        # re-quantizing the whole (V, D) arena
        self.cold_q: Optional[es.QuantizedArena] = None
        self._dirty_q = None
        if cache_cfg is not None and cache_cfg.quantize_cold:
            self.cold_q = es.QuantizedArena.from_arena(params["arena"])
            self._dirty_q = np.zeros(params["arena"].shape[0], bool)
        # tiered maintenance: materialize the TieredSource at construction
        # (uniform histogram) so the treedef is stable from step 0, and
        # track dirtied rows for the incremental migration requant
        self.tiered = None
        self._patch_t = None
        if cache_cfg is not None and cache_cfg.tiers is not None:
            self.tiered = cache_cfg.tiers.build_source(
                params["arena"], self.spec, None, telemetry=self.telemetry)
            self._dirty_q = np.zeros(params["arena"].shape[0], bool)
            self._patch_t = jax.jit(_patch_tiered_hot, static_argnums=(2,))
            self._g_tier_bytes = {
                tier: reg.gauge("rec_tier_bytes",
                                "device bytes held by this storage tier",
                                labels={"tier": tier})
                for tier in ("hot", "warm", "cold", "maps", "host")}
            self._set_tier_gauges()

    def _set_tier_gauges(self):
        from repro import storage
        for tier, nb in storage.tier_bytes(self.tiered).items():
            if tier in self._g_tier_bytes:
                self._g_tier_bytes[tier].set(nb)

    # -- histogram ---------------------------------------------------------

    def observe(self, batch: Dict) -> None:
        """Fold one batch's index stream into the decayed histogram.

        No-op without a ``cache_cfg``: the histogram exists to rank
        rebuilds (hot caches, tier migrations), so an uncached trainer
        skips the host-side row counting entirely instead of silently
        burning a full-arena bincount per batch."""
        if self.cache_cfg is None:
            return
        counts = se.trace_row_counts(self.spec, np.asarray(batch["indices"]),
                                     np.asarray(batch["offsets"]))
        self.hist = self.cache_cfg.decay * self.hist + counts

    # -- training ----------------------------------------------------------

    def train_step(self, batch: Dict) -> float:
        """One optimizer step; maintains the cache protocol as a side effect.

        Traced, the step is a ``train_step`` span (a profiler step
        marker) holding ``feed`` (histogram + host-to-device copy),
        ``step`` (the jitted call's enqueue), ``rows`` (touched-row,
        patch and rebuild handling) and ``loss_sync`` (the host waiting
        for the step's loss)."""
        tel = self.telemetry
        with tel.span("train_step", step_num=self.steps + 1):
            with tel.span("feed"):
                if self.cache_cfg is not None:   # only rebuilds use it
                    self.observe(batch)
                batch_dev = {k: jnp.asarray(v) for k, v in batch.items()
                             if k in ("dense", "indices", "offsets",
                                      "labels")}
            with tel.span("step"):
                self.params, self.opt_state, loss, rows = self._step(
                    self.params, self.opt_state, batch_dev)
            self.steps += 1
            with tel.span("rows"):
                self._after_step(rows)
            with tel.span("loss_sync"):
                loss = float(loss)
        self.losses.append(loss)
        if self.telemetry.enabled:
            self._c_steps.inc()
            self._g_loss.set(loss)
        return loss

    def _after_step(self, rows) -> None:
        if self._dirty_q is not None:
            # the null/fill rows ride along harmlessly: re-quantizing an
            # all-zero row is an exact no-op
            self._dirty_q[np.asarray(rows)] = True
        if self.cache is not None:
            # step 1 of the protocol: values must never go stale
            self.cache = self._patch(self.cache, self.params["arena"],
                                     self.spec.null_row, rows)
        if self.tiered is not None:
            # same step-1 obligation for the tiered hot tier: the fp hot
            # copies refresh every step, warm/cold rows wait (dirty-masked)
            # for the migration pass
            self.tiered = self._patch_t(self.tiered, self.params["arena"],
                                        self.spec.null_row, rows)
        if self.cache_cfg is not None \
                and self.steps % self.cache_cfg.refresh_every == 0:
            self.rebuild_cache()

    def train(self, batches: Iterable[Dict]) -> list:
        for batch in batches:
            self.train_step(batch)
        return self.losses

    # -- cache publication -------------------------------------------------

    def rebuild_cache(self) -> VersionedHotCache:
        """Step 2 of the protocol: re-rank from the decayed histogram and
        publish a fresh cache under a bumped version. When quantized-cold
        maintenance is on, the int8 arena is patched in the same version
        (only the rows dirtied since the last rebuild are re-quantized)."""
        assert self.cache_cfg is not None, "no cache_cfg configured"
        if self.tiered is not None:
            self.version += 1
            self._c_rebuilds.inc()
            self._g_version.set(self.version)
            self.retier()
            return self.snapshot()   # None: tiered serving has no hot-cache
            #                          artifact; publish_source() is the blob
        self.cache = se.build_hot_cache(self.params["arena"], self.spec,
                                        self.hist, self.cache_cfg.k)
        if self.cold_q is not None:
            self.refresh_quantized()
        self.version += 1
        self._c_rebuilds.inc()
        self._g_version.set(self.version)
        self._g_hot_k.set(self.cache_cfg.k)
        self.telemetry.emit("hot_cache_rebuild", version=self.version,
                            step=self.steps, k=self.cache_cfg.k)
        return self.snapshot()

    def refresh_quantized(self) -> es.QuantizedArena:
        """Incremental quantized-cold maintenance: re-quantize exactly the
        rows dirtied since the last refresh (O(touched), not O(V)); the
        result is bit-identical to a full ``QuantizedArena.from_arena``
        rebuild because row-wise quantization has no cross-row state."""
        assert self.cold_q is not None, \
            "no quantized cold arena maintained (cache_cfg.quantize_cold)"
        rows = np.nonzero(self._dirty_q)[0]
        if rows.size:
            self.cold_q = self.cold_q.quantize_rows(
                self.params["arena"], jnp.asarray(rows, jnp.int32))
            self._dirty_q[:] = False
        self._g_requant.set(int(rows.size))
        self.telemetry.emit("quantized_refresh", version=self.version,
                            step=self.steps, rows=int(rows.size))
        return self.cold_q

    def retier(self):
        """Tier-migration maintenance (step 6 of the swap protocol): re-rank
        from the decayed histogram and migrate rows across the fixed-size
        hot/warm/cold tiers. Incremental like ``refresh_quantized`` — rows
        that stayed in tier and were not dirtied keep their old quantized
        values; only movers and dirtied rows re-quantize."""
        from repro import storage
        assert self.tiered is not None, \
            "no tiered source maintained (cache_cfg.tiers)"
        self.tiered, stats = storage.migrate(
            self.tiered, self.params["arena"], self.spec,
            self.cache_cfg.tiers, self.hist, self._dirty_q)
        self._dirty_q[:] = False
        self._set_tier_gauges()
        self._g_requant.set(stats["warm_requant"] + stats["cold_requant"])
        self.telemetry.emit("tier_migration", version=self.version,
                            step=self.steps, **stats)
        return self.tiered

    def snapshot(self) -> Optional[VersionedHotCache]:
        if self.cache is None:
            return None
        return VersionedHotCache(cache=self.cache, version=self.version)

    def serving_source(self) -> es.EmbeddingSource:
        """The source a replica should serve right now: the live hot cache
        over the maintained cold arena (int8 when quantize_cold, else the
        fp arena), row-sharded when the trainer runs on a mesh — the same
        composition a ``RecEngine(source='cached', mesh=...)`` serves, so
        the artifact's structure matches sharded replicas too (a
        replicated consumer simply deserializes without a mesh and the
        ShardedArena wrapper unwraps). Structure-stable across versions,
        so pushing it through ``RecEngine.update_source`` never
        recompiles."""
        if self.tiered is not None:
            return self.tiered
        cold = (self.cold_q if self.cold_q is not None
                else es.FpArena(self.params["arena"]))
        if se.mesh_shards(self.mesh) > 1:
            cold = es.ShardedArena(cold, self.mesh)
        if self.cache is None:
            return cold
        # published at a write-through/rebuild boundary, where the hot
        # copies equal their arena rows by protocol — declare coherence
        # so replicas serve with the fast lowering
        return es.CachedSource(hot=self.cache, cold=cold, coherent=True)

    def publish_source(self, include_head: bool = False) -> Optional[bytes]:
        """Serialize the full serving source as a ``VersionedSource``
        broadcast artifact — the arena-broadcast-for-params item: unlike
        ``publish()`` (hot rows only, params shared by reference), this
        blob carries every sparse-stage parameter a remote replica needs
        (hot rows + the entire cold arena). None before the first rebuild.
        For a tiered trainer the blob carries the whole ``TieredSource``
        (a host-cold tier ships its staged snapshot; the live ``HostStore``
        is process-local and marked ephemeral in the blob).

        ``include_head=True`` additionally ships the dense MLP head
        (bottom/top, plus per-table projections when present), closing
        the last in-process sharing: a remote replica adopts serving
        params AND source from the one blob (``VersionedSource.apply``).
        """
        if self.cache is None and self.tiered is None:
            return None
        blob = VersionedSource(source=self.serving_source(),
                               version=self.version,
                               head=(_dense_head(self.params)
                                     if include_head else None)).serialize()
        self.telemetry.emit("publish", version=self.version,
                            artifact="source", bytes=len(blob))
        return blob

    def publish(self) -> Optional[bytes]:
        """Serialize the current snapshot as a fleet broadcast artifact
        (None before the first rebuild). One blob, N consumers: every
        serving replica calls ``VersionedHotCache.deserialize(blob)
        .apply(engine)`` and adopts version k atomically — no recompile
        (K is unchanged), no per-replica rebuild."""
        snap = self.snapshot()
        if snap is None:
            return None
        blob = snap.serialize()
        self.telemetry.emit("publish", version=snap.version,
                            artifact="hot_cache", bytes=len(blob))
        return blob

    def sync_engine(self, engine) -> bool:
        """Publish the trained state into a RecEngine if it is behind;
        returns True when a swap happened.

        Params and cache swap *together*: hot-row copies are snapshots of
        arena rows, so publishing one without the other would serve a
        hybrid of two arena versions — exactness requires the pair. The
        gate is the trainer *step*, not just the rebuild version: between
        rebuilds every optimizer step advances (params, patched cache) as
        a consistent pair, and serving should track it.

        The push goes through ``update_source`` with a source rebuilt to
        the engine's own structure: the fp cold leaf rebinds to the live
        arena, an int8 cold leaf swaps to the trainer-maintained
        ``cold_q`` (incremental requant) — one atomic swap, no recompile.
        """
        if self.tiered is not None:
            # the tiered trainer has no hot-cache artifact; the pair that
            # must swap together is (params, TieredSource) — same step gate
            if getattr(engine, "_trainer_step", -1) >= self.steps \
                    and getattr(engine, "source_version", -1) >= self.version:
                return False
            engine.params = self.params
            engine.update_source(self.tiered, version=self.version)
            engine._trainer_step = self.steps
            return True
        snap = self.snapshot()
        if snap is None:
            return False
        if getattr(engine, "_trainer_step", -1) >= self.steps \
                and getattr(engine, "cache_version", -1) >= snap.version:
            return False
        engine.params = self.params          # MLPs + fp-arena leaf rebind
        new_source = self._match_structure(engine.source, snap.cache)
        engine.update_source(new_source, version=snap.version)
        engine._trainer_step = self.steps
        return True

    def _match_structure(self, engine_source,
                         cache: se.HotRowCache) -> es.EmbeddingSource:
        """Rebuild the engine's source shape from live trainer state."""
        def cold_like(c):
            if isinstance(c, es.ShardedArena):
                return es.ShardedArena(cold_like(c.inner), c.mesh, c.axis)
            if isinstance(c, es.QuantizedArena):
                assert self.cold_q is not None, \
                    ("the engine serves an int8 cold arena but the "
                     "trainer maintains none — set "
                     "OnlineCacheConfig(quantize_cold=True)")
                return self.cold_q
            if isinstance(c, es.FpArena):
                return es.FpArena(self.params["arena"])
            raise TypeError(f"cannot sync cold source {type(c).__name__}")
        if isinstance(engine_source, es.CachedSource):
            # mirror the engine's coherence declaration: the flag is
            # pytree structure, and a structure mismatch would recompile
            return es.CachedSource(hot=cache,
                                   cold=cold_like(engine_source.cold),
                                   coherent=engine_source.coherent)
        return cold_like(engine_source)


class OnlineGroupTrainer:
    """Per-table online trainer for heterogeneous table groups.

    The group sibling of ``OnlineTrainer``: every piece of protocol state
    goes per-table — one decayed row-frequency histogram, one hot cache
    (only for the tables whose ``TablePlan.cache_k`` > 0: hot-caching a
    near-uniform table buys nothing), one optional int8 mirror (only for
    ``TablePlan.quantize`` tables), and one Adagrad accumulator per
    member arena (inside the group train step). Publication is ONE
    ``VersionedSource`` carrying the whole ``TableGroupSource``, so a
    replica adopts every table's refresh in a single atomic, versioned,
    no-recompile swap — the swap protocol of ``repro.training`` step 4,
    unchanged, just over a bigger pytree.

    Structure stability: caches and int8 mirrors are materialized at
    construction (uniform histogram) rather than at the first rebuild, so
    ``serving_source()`` has the same treedef from step 0 and every
    ``sync_engine`` push hits the engine's compiled executable.
    """

    def __init__(self, cfg: DLRMConfig, params: Dict, *, max_l: int,
                 plans, lr: float = 1e-3, refresh_every: int = 50,
                 decay: float = 0.98,
                 telemetry: Optional[obs.Telemetry] = None):
        assert cfg.heterogeneous, \
            "OnlineGroupTrainer needs a heterogeneous config"
        assert len(plans) == cfg.n_tables, (len(plans), cfg.n_tables)
        self.telemetry = telemetry if telemetry is not None \
            else obs.Telemetry()
        reg = self.telemetry.registry
        self._g_loss = reg.gauge("train_loss", "last optimizer-step loss")
        self._g_version = reg.gauge("train_cache_version",
                                    "last published rebuild version")
        self._c_steps = reg.counter("train_steps_total",
                                    "optimizer steps taken")
        self._c_rebuilds = reg.counter("train_rebuilds_total",
                                       "hot-cache rebuilds")
        self.cfg = cfg
        self.spec = dlrm.arena_spec(cfg)
        self.specs = dlrm.member_specs(cfg)
        self.plans = tuple(plans)
        self.params = params
        self.max_l = max_l
        self.refresh_every = refresh_every
        self.decay = decay
        opt, step = dlrm.make_train_step_ragged(cfg, max_l=max_l, lr=lr,
                                                sparse=True)
        self.opt_state = opt.init(params)
        self._step = jax.jit(step, donate_argnums=(1,))
        self._patch = jax.jit(_patch_hot_rows, static_argnums=(2,))
        self._patch_t = jax.jit(_patch_tiered_hot, static_argnums=(2,))
        self.hists = [np.zeros(sp.total_rows, np.float64)
                      for sp in self.specs]
        self.steps = 0
        self.version = 0
        self.losses: list = []
        self.caches = []
        self.cold_q = []
        self.tiered = []
        self._dirty_q = []
        for plan, sp, arena in zip(self.plans, self.specs,
                                   params["tables"]):
            self.caches.append(
                se.build_hot_cache(arena, sp, np.ones(sp.total_rows),
                                   plan.cache_k)
                if plan.cache_k > 0 else None)
            self.cold_q.append(es.QuantizedArena.from_arena(arena)
                               if plan.quantize else None)
            self.tiered.append(
                plan.tiers.build_source(arena, sp, None,
                                        telemetry=self.telemetry)
                if getattr(plan, "tiers", None) is not None else None)
            self._dirty_q.append(
                np.zeros(arena.shape[0], bool)
                if (plan.quantize or self.tiered[-1] is not None) else None)

    # -- histogram ---------------------------------------------------------

    def observe(self, batch: Dict) -> None:
        """Fold one interleaved batch into the per-table histograms."""
        counts = es.group_trace_counts(self.specs, batch["indices"],
                                       batch["offsets"])
        for t, c in enumerate(counts):
            self.hists[t] = self.decay * self.hists[t] + c

    # -- training ----------------------------------------------------------

    def train_step(self, batch: Dict) -> float:
        """One optimizer step; per-table write-through patch rides along.
        Traced with the same spans as ``OnlineTrainer.train_step``."""
        tel = self.telemetry
        with tel.span("train_step", step_num=self.steps + 1):
            with tel.span("feed"):
                self.observe(batch)
                batch_dev = {k: jnp.asarray(v) for k, v in batch.items()
                             if k in ("dense", "indices", "offsets",
                                      "labels")}
            with tel.span("step"):
                self.params, self.opt_state, loss, touched = self._step(
                    self.params, self.opt_state, batch_dev)
            self.steps += 1
            with tel.span("rows"):
                self._after_step(touched)
            with tel.span("loss_sync"):
                loss = float(loss)
        self.losses.append(loss)
        if self.telemetry.enabled:
            self._c_steps.inc()
            self._g_loss.set(loss)
        return loss

    def _after_step(self, touched) -> None:
        for t, rows in enumerate(touched):
            if self._dirty_q[t] is not None:
                self._dirty_q[t][np.asarray(rows)] = True
            if self.caches[t] is not None:
                self.caches[t] = self._patch(
                    self.caches[t], self.params["tables"][t],
                    self.specs[t].null_row, rows)
            if self.tiered[t] is not None:
                self.tiered[t] = self._patch_t(
                    self.tiered[t], self.params["tables"][t],
                    self.specs[t].null_row, rows)
        if self.steps % self.refresh_every == 0:
            self.rebuild()

    def train(self, batches: Iterable[Dict]) -> list:
        for batch in batches:
            self.train_step(batch)
        return self.losses

    # -- publication -------------------------------------------------------

    def rebuild(self) -> int:
        """Re-rank every cached table from its decayed histogram, patch
        every int8 mirror (only the rows dirtied since the last rebuild),
        and bump ONE version for the whole group — tables refresh
        together or not at all, so a replica can never serve a torn mix
        of table versions."""
        from repro import storage
        requant = {}
        migrated = {}
        for t, (plan, sp) in enumerate(zip(self.plans, self.specs)):
            if plan.cache_k > 0:
                self.caches[t] = se.build_hot_cache(
                    self.params["tables"][t], sp, self.hists[t],
                    plan.cache_k)
            if self.cold_q[t] is not None:
                rows = np.nonzero(self._dirty_q[t])[0]
                requant[str(t)] = int(rows.size)
                if rows.size:
                    self.cold_q[t] = self.cold_q[t].quantize_rows(
                        self.params["tables"][t],
                        jnp.asarray(rows, jnp.int32))
                    self._dirty_q[t][:] = False
            if self.tiered[t] is not None:
                self.tiered[t], stats = storage.migrate(
                    self.tiered[t], self.params["tables"][t], sp,
                    plan.tiers, self.hists[t], self._dirty_q[t])
                self._dirty_q[t][:] = False
                migrated[str(t)] = stats
        self.version += 1
        self._c_rebuilds.inc()
        self._g_version.set(self.version)
        self.telemetry.emit(
            "hot_cache_rebuild", version=self.version, step=self.steps,
            cached_tables=[t for t, c in enumerate(self.caches)
                           if c is not None],
            requant_rows=requant)
        if migrated:
            self.telemetry.emit("tier_migration", version=self.version,
                                step=self.steps, tables=migrated)
        return self.version

    def serving_source(self) -> es.TableGroupSource:
        """The group a replica should serve right now (same structure at
        every step — see the class docstring)."""
        members = []
        for t, plan in enumerate(self.plans):
            if self.tiered[t] is not None:
                members.append(self.tiered[t])
                continue
            cold = (self.cold_q[t] if self.cold_q[t] is not None
                    else es.FpArena(self.params["tables"][t]))
            members.append(es.CachedSource(hot=self.caches[t], cold=cold,
                                           coherent=True)
                           if self.caches[t] is not None else cold)
        return es.TableGroupSource(members=tuple(members),
                                   specs=self.specs)

    def publish_source(self, include_head: bool = False) -> bytes:
        """One ``VersionedSource`` blob carrying every table's sparse
        params (hot rows + cold arenas) under the group's single
        version; ``include_head=True`` adds the dense MLP head so remote
        replicas need no in-process parameter sharing."""
        blob = es.VersionedSource(source=self.serving_source(),
                                  version=self.version,
                                  head=(_dense_head(self.params)
                                        if include_head else None)
                                  ).serialize()
        self.telemetry.emit("publish", version=self.version,
                            artifact="group_source", bytes=len(blob))
        return blob

    def sync_engine(self, engine) -> bool:
        """Push the live group into a RecEngine if it is behind (same
        step-gate as ``OnlineTrainer.sync_engine``; params and source
        swap together)."""
        if getattr(engine, "_trainer_step", -1) >= self.steps \
                and engine.source_version >= self.version:
            return False
        engine.params = self.params
        engine.update_source(self.serving_source(), version=self.version)
        engine._trainer_step = self.steps
        return True


def make_drifting_zipf(cfg: DLRMConfig, *, batch_size: int, mean_l: int,
                       max_l: int, drift_per_batch: int = 0,
                       alpha: float = 1.05, seed: int = 0):
    """Ragged-batch generator whose hot set rotates over time.

    Zipf rank r maps to row (r + t * drift_per_batch) % rows at batch t, so
    the most popular rows shift by `drift_per_batch` every batch — the
    RecNMP drift scenario an offline-built cache cannot follow. Yields
    batches shaped exactly like DLRMSynthetic.ragged_batch, padded to a
    static stream length so every batch hits one compiled shape.
    """
    rng = np.random.RandomState(seed)
    w = rng.randn(cfg.dense_features).astype(np.float32)
    n_bags = batch_size * cfg.n_tables
    pad_to = n_bags * max_l
    t = 0
    while True:
        lens = np.clip(rng.poisson(mean_l, n_bags), 0, max_l).astype(np.int32)
        offsets = np.zeros(n_bags + 1, np.int32)
        np.cumsum(lens, out=offsets[1:])
        n = int(offsets[-1])
        raw = rng.zipf(alpha, size=n)
        shifted = (raw - 1) + t * drift_per_batch
        if cfg.heterogeneous:
            # fold each position into its own table's vocab (per-table
            # skew comes from table_alphas at generation time elsewhere;
            # here the drift scenario keeps one shared alpha)
            seg = np.searchsorted(offsets[1:], np.arange(n), side="right")
            rows = np.asarray(cfg.resolved_table_rows)
            indices = (shifted % rows[seg % cfg.n_tables]).astype(np.int32)
        else:
            indices = (shifted % cfg.rows_per_table).astype(np.int32)
        indices = np.concatenate([indices, np.zeros(pad_to - n, np.int32)])
        dense = rng.randn(batch_size, cfg.dense_features).astype(np.float32)
        logit = dense @ w * 0.5
        labels = (rng.rand(batch_size)
                  < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
        yield {"dense": dense, "indices": indices, "offsets": offsets,
               "lengths": lens, "labels": labels, "max_l": max_l}
        t += 1
