"""Recommendation serving engine — the paper's actual deployment target.

The LM engine (``engine.py``) serves token streams; this engine serves
CTR-prediction traffic (paper Section IV-A: user-facing inference with firm
SLAs) over the ragged production sparse path:

* ``RecRequest`` — one user impression: dense features + per-table ragged
  sparse id lists (the SparseLengthsSum format of paper Fig. 2);
* ``RecBatcher`` — admission queue with (max_batch, max_wait_ms)
  micro-batching, the standard SLA/throughput knob;
* ``RecEngine`` — drains the batcher, pads each micro-batch to a static
  *bucket* shape (batch rounded up to a bucket size with empty-bag dummy
  rows, flat index stream padded to bucket*T*max_l) so every bucket
  compiles exactly once, then serves one ragged forward whose embedding
  stage is a single ``embedding_source.lookup_bags`` over the engine's
  ``EmbeddingSource`` pytree.

  WHICH source serves is a declarative plan, not a kwarg soup: the engine
  takes ``source=`` as a ``SourceSpec`` (or an already-built
  ``EmbeddingSource``), with the old path strings kept as thin aliases:

    - ``"fixed"``   — legacy fixed-L layout (regression baseline);
    - ``"ragged"``  — fp arena, row-sharded when the plan has a mesh;
    - ``"sharded"`` — ragged with the mesh *required* (a misconfigured
                      replica can never silently fall back to replicated);
    - ``"cached"``  — hot-row cache over any cold source (fp or int8,
                      replicated or sharded).

  The source is a call-time jit argument, so ``update_source`` swaps ANY
  component — hot cache, quantized cold arena, the full fp arena —
  without recompiling (same treedef + leaf shapes = compiled-cache hit),
  and stale (lower-version) swaps are rejected at this boundary.

  Telemetry is first-class (``repro.obs``): the engine takes a
  ``Telemetry`` bundle and backs everything observable with it —
  bounded-memory latency/batch-size histograms (O(1) in requests served;
  the old unbounded ``latencies``/``batch_sizes`` lists survive only as
  ring-backed compatibility properties), per-request spans through
  ``enqueue → batch → bucket_pad → forward → respond`` (or the per-stage
  split below; continuous batching traces ``dispatch → bucket_pad`` and
  ``settle → settle_wait, respond``), and a structured event log of the
  swap protocol with per-version hit-rate attribution
  (``telemetry.events.hit_rate_by_version()``).

  Hit-rate accounting never adds a device sync to the hot path: the
  per-batch probe is *dispatched* in ``step()`` but only *collected*
  (host conversion of the result futures) at ``stats()`` / ``drain()`` /
  swap boundaries, or when the pending queue hits ``PENDING_MAX``
  entries — by which point those futures completed long ago.

  ``Telemetry(device_stages=True)`` serves through separately jitted
  pipeline stages with a sync between each, attributing *device* time to
  sparse lookup vs. interaction vs. top MLP — the paper's Fig-5
  embedding-vs-MLP characterization measured live (``live_fig5()``).

  Per-request latency percentiles (p50/p95/p99) are exported by
  ``stats()`` — cumulative plus ``since_swap``/``rolling`` windows so a
  post-swap regression is visible instead of averaged away; hit-rate
  accounting is per-path-correct: a non-cached source reports
  ``cache_hit_rate=None`` (never a fake 0.0), and the counters reset on
  version bumps so the post-swap rate reflects the live cache.
"""
from __future__ import annotations

import itertools
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import DLRMConfig
from repro.core import dlrm
from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.core.embedding_source import SourceSpec


@dataclass
class RecRequest:
    rid: int
    dense: np.ndarray                   # (dense_features,) float32
    sparse_ids: List[np.ndarray]        # per table: (l_t,) int32, l_t<=max_l
    # wall-clock stamps are USER-FACING only (log lines, dashboards);
    # every deadline / latency computation runs on submitted_mono — an
    # NTP step must never flush a batch early or stall it past its wait
    # budget, and must never corrupt a recorded latency
    submitted_at: float = field(default_factory=time.time)
    submitted_mono: float = field(default_factory=time.monotonic)
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    prob: Optional[float] = None        # predicted CTR, set when served
    shed: bool = False                  # dropped at admission (SLA)
    downgraded: bool = False            # served on the int8 downgrade path
    # (per_id, table) id streams for host-cold staging, extracted once at
    # admission (engine-internal; None until an engine with a host cold
    # tier touches the request)
    cold_streams: Optional[tuple] = None


class RecBatcher:
    """Admission queue: release a micro-batch when it is full or when the
    oldest request has waited max_wait_ms (the SLA knob).

    Deadline math runs on the monotonic clock (``clock`` is injectable
    for tests) against ``RecRequest.submitted_mono`` — wall clock is
    kept only for the user-facing ``submitted_at`` stamp.
    """

    def __init__(self, max_batch: int = 32, max_wait_ms: float = 2.0,
                 clock=time.monotonic):
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self._clock = clock
        self._queue: List[RecRequest] = []

    def __len__(self) -> int:
        return len(self._queue)

    def submit(self, req: RecRequest):
        self._queue.append(req)

    def take(self, force: bool = False) -> List[RecRequest]:
        if not self._queue:
            return []
        oldest = self._clock() - self._queue[0].submitted_mono
        if force or len(self._queue) >= self.max_batch \
                or oldest * 1e3 >= self.max_wait_ms:
            batch = self._queue[:self.max_batch]
            self._queue = self._queue[self.max_batch:]
            return batch
        return []


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def tune_buckets(sizes: Sequence[int], max_batch: int,
                 n_buckets: int = 6) -> tuple:
    """Pick pad-bucket boundaries from an observed micro-batch-size
    histogram instead of fixed powers of two.

    Boundaries are the ceil-quantiles of the observed sizes (equal traffic
    mass per bucket), deduplicated, with max_batch always present as the
    catch-all. Fewer distinct observed sizes than n_buckets simply yields
    fewer buckets — each observed size then pads to itself (zero waste).
    Observed sizes above max_batch clip to it: the batcher never releases
    more than max_batch, so a larger bucket would only be compiled, never
    hit.
    """
    if len(sizes) == 0:
        return tuple(sorted({1, max_batch}))
    arr = np.sort(np.minimum(np.asarray(sizes, np.int64), max_batch))
    qs = [arr[min(len(arr) - 1, int(np.ceil((i + 1) / n_buckets * len(arr)))
                 - 1)] for i in range(n_buckets)]
    out = sorted({int(q) for q in qs if q >= 1} | {max_batch})
    return tuple(out)


@dataclass
class InflightBatch:
    """A dispatched-but-unsettled micro-batch: the device-array future
    plus just enough host context to account for it at settle time.
    Produced by ``RecEngine.dispatch``, consumed by ``RecEngine.settle``
    — the unit of continuous batching (``repro.serving.scheduler``)."""
    reqs: List[RecRequest]
    probs: object                       # device array, NOT host-converted
    bucket: int
    downgraded: bool
    dispatched_mono: float
    seq: int = 0                        # the engine's dispatch number: the
    #                                     ``batch`` attr of its dispatch and
    #                                     settle spans


_STAGE_NAMES = ("sparse_lookup", "interaction", "mlp")


class RecEngine:
    """Batcher-fed DLRM inference; the embedding stage is ONE
    ``lookup_bags`` over a swappable ``EmbeddingSource`` pytree.

    ``source`` accepts:
      * a path string — ``'fixed' | 'ragged' | 'sharded' | 'cached'`` —
        the thin aliases onto a ``SourceSpec`` (cache_k / quantize_cold /
        mesh feed the plan);
      * a ``SourceSpec`` — the declarative plan, built against
        ``params['arena']`` (+ ``cache_trace`` for the hot ranking); a
        plan with ``tables=`` (per-table ``TablePlan``s) builds a
        heterogeneous ``TableGroupSource`` from ``params['tables']`` and
        a *list* of per-table trace histograms, and ``stats()`` reports
        ``cache_hit_rate`` as a per-table mapping (None for members
        without a hot cache);
      * an ``EmbeddingSource`` — served as-is (ragged layout).

    ``telemetry`` is the ``repro.obs.Telemetry`` bundle (default: metrics
    on, tracing off). ``obs.Telemetry.disabled()`` serves genuinely
    uninstrumented — nothing recorded, no hit-rate probe dispatched (the
    ``obs_overhead`` benchmark baseline).
    """

    PATHS = SourceSpec.PATH_NAMES
    # pending hit-rate probes are collected (host-converted) past this
    # depth; by then the oldest futures completed many batches ago, so
    # the conversion is a read, not a stall
    PENDING_MAX = 64

    def __init__(self, cfg: DLRMConfig, params: Dict, *,
                 source: Union[str, SourceSpec, es.EmbeddingSource,
                               None] = None,
                 max_l: Optional[int] = None,
                 max_batch: int = 32, max_wait_ms: float = 2.0,
                 buckets: Sequence[int] = (1, 2, 4, 8, 16, 32),
                 cache_k: int = 0, cache_trace=None,
                 quantize_cold: bool = False,
                 auto_tune_after: Optional[int] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 telemetry: Optional[obs.Telemetry] = None,
                 path: Optional[str] = None):
        if path is not None:
            warnings.warn(
                "RecEngine(path=...) is deprecated; pass source=<path "
                "string | SourceSpec | EmbeddingSource> instead",
                DeprecationWarning, stacklevel=2)
            assert source is None, "pass source= OR path=, not both"
            source = path
        self.cfg = cfg
        self.source: Optional[es.EmbeddingSource] = None
        self.params = params
        self.spec = dlrm.arena_spec(cfg)
        self.max_l = max_l if max_l is not None else cfg.lookups_per_table
        self.mesh = mesh
        self.batcher = RecBatcher(max_batch, max_wait_ms)
        self.max_batch = max_batch
        self.buckets = tuple(sorted(set(buckets) | {max_batch}))
        self.auto_tune_after = auto_tune_after
        self._retuned = False
        self.served = 0
        self._hits = 0.0                     # per-table arrays for groups
        self._lookups = 0
        self._pending: List[tuple] = []      # dispatched, uncollected probes
        self.source_version = 0
        self._next_swap_kind = "source_swap"

        self.telemetry = telemetry if telemetry is not None \
            else obs.Telemetry()
        reg = self.telemetry.registry
        self._lat_hist = reg.histogram(
            "rec_request_latency_ms", "end-to-end request latency",
            lo=1e-3, hi=1e5, ring=4096)
        self._batch_hist = reg.histogram(
            "rec_batch_size", "released micro-batch sizes",
            lo=1.0, hi=4096.0, growth=1.25, ring=256)
        self._c_served = reg.counter("rec_requests_total",
                                     "requests served")
        self._c_batches = reg.counter("rec_batches_total",
                                      "micro-batches served")
        self._c_swaps = reg.counter("rec_source_swaps_total",
                                    "accepted source/cache swaps")
        self._c_stale = reg.counter("rec_stale_rejected_total",
                                    "rejected stale broadcasts")
        self._g_version = reg.gauge("rec_source_version",
                                    "currently served source version")
        self._g_queue = reg.gauge("rec_queue_depth",
                                  "admission-queue depth (set on enqueue "
                                  "and after every serve/drain)")
        self._qwait_hist = reg.histogram(
            "rec_queue_wait_ms", "admission-to-dispatch queue wait",
            lo=1e-3, hi=1e5, ring=4096)
        self._c_cold = reg.counter(
            "rec_cold_compiles_total",
            "dispatches that hit a cold (path, bucket) compile-cache "
            "entry — zero after warmup() is the warm-pool claim")
        # the warm compile-cache pool: (path kind, bucket) pairs whose
        # compiled entry has been triggered (warmup() or first dispatch)
        self._warm: set = set()
        self._down_source: Optional[es.EmbeddingSource] = None
        # auto-tune sampling is capped at auto_tune_after (satellite of
        # the unbounded-lists fix): the tuner never needs more history
        self._batch_ring: deque = deque(
            maxlen=max(1024, auto_tune_after or 0))
        self._batches_seen = 0
        self._dispatch_seq = itertools.count()

        if source is None:
            source = "ragged"
        if isinstance(source, (str, SourceSpec)):
            self.plan: Optional[SourceSpec] = SourceSpec.from_path(
                source, cache_k=cache_k, quantize_cold=quantize_cold,
                mesh=mesh)
            self.path = self.plan.path_name()
            # a table-group plan builds from the per-table arenas (and a
            # LIST of per-table trace histograms)
            arena = (params["tables"] if self.plan.tables is not None
                     else params["arena"])
            self.source = self.plan.build(arena, self.spec, cache_trace)
        else:
            assert isinstance(source, es.EmbeddingSource), source
            assert not cache_k and cache_trace is None \
                and not quantize_cold, \
                ("cache_k/cache_trace/quantize_cold are SourceSpec plan "
                 "inputs; a pre-built EmbeddingSource is served as-is — "
                 "compose a CachedSource/QuantizedArena yourself or pass "
                 "a SourceSpec instead of silently dropping the kwargs")
            self.plan = None
            self.path = es.describe_source(source)
            self.source = source
        self.layout = ("fixed" if self.plan is not None
                       and self.plan.layout == "fixed" else "ragged")

        if self.layout == "fixed":
            step = dlrm.make_serve_step(cfg, mesh)
            self._serve = jax.jit(step)
        else:
            # the source is a call-time pytree argument so update_source
            # can swap any component — hot cache, int8 cold arena, the
            # full fp arena — without recompiling (same treedef + leaf
            # shapes = compiled-cache hit)
            step = dlrm.make_ragged_serve_step(cfg, max_l=self.max_l,
                                               mesh=mesh)
            self._serve = jax.jit(step)
        self._staged = None
        if self.telemetry.device_stages:
            assert self.layout != "fixed", \
                ("device_stages (live Fig-5) characterizes the ragged "
                 "pipeline; the fixed layout has no staged serve path")
            sp, it, tp = dlrm.make_ragged_serve_stages(
                cfg, max_l=self.max_l, mesh=mesh)
            self._staged = (jax.jit(sp), jax.jit(it), jax.jit(tp))
        if self.grouped:
            # the whole source is the jit argument, so per-table hit
            # accounting survives every no-recompile member swap; the
            # engine's static max_l lets the counters ride the same
            # one-relayout fused dispatch as the lookup itself
            self._hit_rate = jax.jit(
                lambda s, i, o: es.group_hit_counts(s, i, o,
                                                    max_l=self.max_l))
        else:
            self._hit_rate = jax.jit(
                lambda c, i, o: se.cache_hit_rate(c, self.spec, i, o))
        self._reset_hit_counters()
        self._bind_host_stores()
        self._g_version.set(self.source_version)

    @property
    def grouped(self) -> bool:
        """Serving a heterogeneous TableGroupSource?"""
        return isinstance(self.source, es.TableGroupSource)

    # -- host cold tier: staging + prefetch ---------------------------------

    def _bind_host_stores(self) -> None:
        """Discover the host-resident cold stores (if any) behind the
        served source and adopt them into the engine's telemetry. Grouped
        sources keep the owning table alongside each store: staging wants
        per-table ids for members, flattened arena ids otherwise."""
        from repro import storage
        self._host_stores: List[tuple] = []
        if self.source is None or self.layout == "fixed":
            return
        if self.grouped:
            for t, m in enumerate(self.source.members):
                for st in storage.host_stores_of(m):
                    self._host_stores.append((st, t))
        else:
            for st in storage.host_stores_of(self.source):
                self._host_stores.append((st, None))
        for st, _ in self._host_stores:
            st.bind_telemetry(self.telemetry)
        self._stream_cache = None

    def _req_streams(self, r: RecRequest) -> tuple:
        """One request's (per_id, table) id streams, extracted once —
        ``submit`` computes this at admission so the dispatch hot path
        only concatenates (requests staged ahead through ``prefetch``
        fill theirs on first touch)."""
        s = r.cold_streams
        if s is None:
            t = self.cfg.n_tables
            lens = np.fromiter(map(len, r.sparse_ids), np.int64, count=t)
            per_id = (np.concatenate(r.sparse_ids).astype(
                np.int64, copy=False) if int(lens.sum())
                else np.zeros(0, np.int64))
            tbl = np.repeat(np.arange(t, dtype=np.int64), lens)
            s = r.cold_streams = (per_id, tbl)
        return s

    def _host_id_streams(self, reqs: List[RecRequest]):
        """The id streams the host stores need, host-side numpy only:
        per-table ids for grouped members, flattened arena ids (per-table
        id + table base) for a homogeneous source. Never reads a device
        array — staging must not sync the serve path. Per-request
        extraction happened at admission; this only concatenates."""
        empty = np.zeros(0, np.int64)
        if not reqs:
            return empty, {}
        parts = [self._req_streams(r) for r in reqs]
        per_id = np.concatenate([p[0] for p in parts])
        tbl = np.concatenate([p[1] for p in parts])
        flat = (per_id + tbl * self.spec.rows_per_table
                if any(tt is None for _, tt in self._host_stores)
                else empty)
        per_table = {j: per_id[tbl == j]
                     for j in {tt for _, tt in self._host_stores
                               if tt is not None}}
        return flat, per_table

    @staticmethod
    def _ids_for(streams, t, _empty=np.zeros(0, np.int64)):
        flat, per_table = streams
        return flat if t is None else per_table.get(t, _empty)

    def _stage_batch(self, reqs: List[RecRequest], *,
                     ahead: bool = False) -> None:
        """Residency guarantee (``ahead=False``, counted as hits/misses)
        or prefetch (``ahead=True``, uncounted) for one micro-batch's
        cold rows, then refresh the HostTier leaves in the served source.
        Same treedef and leaf shapes — no version bump, no recompile; the
        transfers are async ``device_put``s, so no host sync either.

        The batch path folds the admission queue's NEXT micro-batch into
        the same flush (one transfer + scatter per step, not two) and
        remembers its per-store cold sets — when that batch arrives, the
        extraction, the uniquify, and the transfer have all already
        happened, so it pays only the residency check. That is the
        prefetcher: misses become hits one dispatch ahead of their
        batch."""
        if not self._host_stores or not reqs:
            return
        from repro import storage
        if ahead:
            streams = self._host_id_streams(reqs)
            for st, t in self._host_stores:
                st.prefetch_arena(self._ids_for(streams, t))
            self.source = storage.refresh_host_tiers(self.source)
            return
        cache, self._stream_cache = self._stream_cache, None
        if cache is not None and cache[0] == [r.rid for r in reqs]:
            cur_cold = cache[1]
        else:
            streams = self._host_id_streams(reqs)
            cur_cold = [st.cold_ids_of(self._ids_for(streams, t))
                        for st, t in self._host_stores]
        nxt = list(self.batcher._queue[:self.max_batch])
        nxt_cold = None
        if nxt:
            nstreams = self._host_id_streams(nxt)
            nxt_cold = [st.cold_ids_of(self._ids_for(nstreams, t))
                        for st, t in self._host_stores]
        for i, (st, t) in enumerate(self._host_stores):
            st.stage(cur_cold[i],
                     ahead=None if nxt_cold is None else nxt_cold[i])
        if nxt_cold is not None:
            self._stream_cache = ([r.rid for r in nxt], nxt_cold)
        self.source = storage.refresh_host_tiers(self.source)

    def prefetch(self, reqs: List[RecRequest]) -> None:
        """Stage a future micro-batch's cold rows ahead of its dispatch
        (no hit/miss accounting — prefetched rows count as *hits* when
        their batch arrives; rows pinned by the in-flight batch are never
        evicted). The engine already prefetches the admission queue's
        next micro-batch inside every staged dispatch; this is for
        lookahead the queue can't see yet."""
        self._stage_batch(reqs, ahead=True)

    def _reset_hit_counters(self) -> None:
        if self.grouped:
            t = len(self.source.members)
            self._hits = np.zeros(t, np.int64)
            self._lookups = np.zeros(t, np.int64)
        else:
            self._hits = 0.0
            self._lookups = 0

    # -- bounded-memory compatibility views ---------------------------------

    @property
    def latencies(self) -> List[float]:
        """Most recent per-request latencies in seconds (ring-backed
        compatibility view of the old unbounded list; capped at the
        latency histogram's ring size)."""
        return [v / 1e3 for v in self._lat_hist.ring_values()]

    @property
    def batch_sizes(self) -> List[int]:
        """Most recent observed micro-batch sizes (ring-backed; capped
        at max(1024, auto_tune_after) — all the tuner ever reads)."""
        return list(self._batch_ring)

    # -- the swap boundary --------------------------------------------------

    @property
    def params(self) -> Dict:
        return self._params

    @params.setter
    def params(self, params: Dict) -> None:
        """Swapping the live params rebinds the source's fp-arena leaves,
        so 'params and cache swap together' keeps meaning one assignment
        plus one ``update_cache`` — exactly the pre-API protocol. The
        rebound source has identical leaf shapes, so no recompile."""
        self._params = params
        if getattr(self, "source", None) is not None:
            arena = (params["tables"]
                     if isinstance(self.source, es.TableGroupSource)
                     else params["arena"])
            self.source = es.rebind_arena(self.source, arena)
        if getattr(self, "_down_source", None) is not None:
            # the downgrade arena is derived from the live params, so a
            # params swap requantizes it (same shapes — no recompile)
            self._down_source = self._build_downgrade_source()

    @property
    def cache(self) -> Optional[se.HotRowCache]:
        """The hot cache currently served (None on non-cached sources)."""
        return es.hot_cache_of(self.source)

    @property
    def cache_version(self) -> int:
        """Back-compat alias for ``source_version``."""
        return self.source_version

    def _hit_snapshot(self) -> Dict:
        """Host ints/floats of the live version's hit accounting (for
        swap-event attribution). Collect pending probes first."""
        self._collect_pending()
        if self.grouped:
            return {"hits": float(np.sum(self._hits)),
                    "lookups": float(np.sum(self._lookups)),
                    "per_table": {
                        str(t): (float(self._hits[t]),
                                 float(self._lookups[t]))
                        for t in range(len(self._hits))}}
        return {"hits": float(self._hits),
                "lookups": float(self._lookups)}

    def update_source(self, source: es.EmbeddingSource,
                      version: Optional[int] = None) -> None:
        """Atomically swap the served embedding source (any component:
        hot cache, quantized cold arena, full fp arena).

        The whole source pytree is replaced at once — no torn state. The
        new source must match the old one's treedef and leaf shapes /
        dtypes, which is exactly the no-recompile condition: the jit'd
        serve step sees the same compiled signature.

        Stale broadcasts are rejected: a versioned swap to anything below
        the currently served version would re-serve rows the trainer has
        since rewritten (broadcast artifacts arrive out of order across a
        fleet). Equal versions are allowed — between rebuilds the trainer
        republishes the same version with write-through-patched values.
        Hit/lookup counters reset on version bumps so the reported hit
        rate reflects the live cache, not its predecessors — the
        outgoing version's totals are attributed to it in the swap event
        (``telemetry.events.hit_rate_by_version()``), and the
        since-swap latency window restarts.
        """
        kind = self._next_swap_kind
        self._next_swap_kind = "source_swap"
        assert self.layout != "fixed", \
            ("a fixed-layout engine serves from params['arena'] and "
             "never reads engine.source — accepting this swap would "
             "bump the version while serving stale embeddings forever")
        if version is not None and version < self.source_version:
            self._c_stale.inc()
            self.telemetry.emit("stale_rejected", version=version,
                                served_version=self.source_version,
                                swap_kind=kind)
            raise ValueError(
                f"stale source broadcast: version {version} < served "
                f"version {self.source_version} — reordered artifact, "
                f"refusing to roll the serving source back")
        old_leaves, old_def = jax.tree_util.tree_flatten(self.source)
        new_leaves, new_def = jax.tree_util.tree_flatten(source)
        assert old_def == new_def, \
            ("source swap changed the pytree structure — this forces a "
             "recompile on the serving hot path", old_def, new_def)
        assert all(a.shape == b.shape and a.dtype == b.dtype
                   for a, b in zip(old_leaves, new_leaves)), \
            ("source swap changed leaf shapes/dtypes — this forces a "
             "recompile on the serving hot path; keep trainer and engine "
             "cache_k / arena shapes equal")
        new_version = (version if version is not None
                       else self.source_version + 1)
        self.source = source
        # a pushed source may carry its own HostStore instances (same
        # structural signature, hence the treedef assert above passed) —
        # re-discover so staging/prefetch target the live stores
        self._bind_host_stores()
        if new_version > self.source_version:
            # per-path-correct accounting: the old cache's hits must not
            # dilute the post-swap hit rate — snapshot them into the
            # swap event (per-version attribution), then reset
            if self.telemetry.enabled:
                snap = self._hit_snapshot()
                self.telemetry.emit(kind, version=new_version,
                                    prev_version=self.source_version,
                                    **snap)
                self._lat_hist.reset_window()
            self._c_swaps.inc()
            self._reset_hit_counters()
        else:
            self.telemetry.emit(kind, version=new_version,
                                republish=True)
        self.source_version = new_version
        self._g_version.set(new_version)

    def update_cache(self, cache: se.HotRowCache,
                     version: Optional[int] = None) -> None:
        """Swap only the hot cache, keeping the cold source (the classic
        online-training refresh; see ``update_source`` for the rules)."""
        assert isinstance(self.source, es.CachedSource), \
            "update_cache needs a cached source"
        if version is not None and version < self.source_version:
            self._c_stale.inc()
            self.telemetry.emit("stale_rejected", version=version,
                                served_version=self.source_version,
                                swap_kind="cache_swap")
            raise ValueError(
                f"stale cache broadcast: version {version} < served "
                f"version {self.source_version} — reordered artifact, "
                f"refusing to roll the hot arena back")
        assert cache.hot_rows.shape == self.source.hot.hot_rows.shape, \
            ("cache swap changed K/D — this forces a recompile on the "
             "serving hot path; keep trainer and engine cache_k equal",
             cache.hot_rows.shape, self.source.hot.hot_rows.shape)
        self._next_swap_kind = "cache_swap"
        try:
            self.update_source(es.with_hot_cache(self.source, cache),
                               version=version)
        finally:
            self._next_swap_kind = "source_swap"

    # -- the int8 downgrade path --------------------------------------------

    @property
    def downgrade_source(self) -> Optional[es.EmbeddingSource]:
        """The int8 source overload batches serve from (None until
        ``enable_downgrade``)."""
        return self._down_source

    def enable_downgrade(self) -> es.EmbeddingSource:
        """Build (once) the int8 downgrade source the SLA scheduler
        serves from under overload.

        No second jit: the downgrade source is just another call-time
        pytree through the SAME ragged serve step, so its treedef gets
        its own compile-cache entry (pre-triggered by ``warmup()`` —
        the warm pool covers both treedefs per bucket) and per-batch
        path selection never recompiles. ``update_source``'s structural
        no-recompile assert only guards primary-source swaps.
        """
        assert self.layout != "fixed", \
            ("the downgrade path serves through the ragged lookup_bags "
             "step; the fixed layout reads params['arena'] directly")
        if self._down_source is None:
            self._down_source = self._build_downgrade_source()
        return self._down_source

    def _build_downgrade_source(self) -> es.EmbeddingSource:
        if self.grouped:
            return es.TableGroupSource(
                members=tuple(es.QuantizedArena.from_arena(a)
                              for a in self.params["tables"]),
                specs=self.source.specs)
        return es.QuantizedArena.from_arena(self.params["arena"])

    def warmup(self):
        """Compile every (path, bucket) pair off the SLA clock — the
        warm compile-cache pool.

        Without this the first live request landing in each bucket pays
        that bucket's jit compile (hundreds of ms) — a p99 spike that
        would show up as an SLA violation in production. With the
        downgrade path enabled both source treedefs are pre-compiled
        per bucket, so in-flight refill never stalls on a compile
        (``rec_cold_compiles_total`` stays zero).
        """
        t = self.cfg.n_tables
        l = self.cfg.lookups_per_table if self.layout == "fixed" else 0
        dummy = [RecRequest(
            rid=-1, dense=np.zeros(self.cfg.dense_features, np.float32),
            sparse_ids=[np.zeros(l, np.int32)] * t)]
        for st, _ in self._host_stores:
            # compile the staging scatter at every flush chunk size off
            # the SLA clock, so the first live miss (and the first
            # miss burst) pays a dispatch, not a jit
            st.warm_compile()
        for bucket in self.buckets:
            batch, _ = self._assemble(dummy, bucket)
            np.asarray(self._run_serve(batch))
            self._warm.add(("primary", bucket))
            if self._down_source is not None:
                np.asarray(self._serve(self.params, batch,
                                       self._down_source))
                self._warm.add(("downgrade", bucket))
            if self._staged is not None:
                sp, it, tp = self._staged
                emb = sp(self.params, batch, self.source)
                np.asarray(tp(self.params, it(self.params, batch, emb)))
            if not self.telemetry.enabled:
                continue            # uninstrumented: probe never runs
            if self.grouped:
                h, _ = self._hit_rate(self.source, batch["indices"],
                                      batch["offsets"])
                h.block_until_ready()
            elif self.cache is not None:
                self._hit_rate(self.cache, batch["indices"],
                               batch["offsets"]).block_until_ready()

    def _run_serve(self, batch: Dict):
        if self.layout == "fixed":
            return self._serve(self.params, batch)
        return self._serve(self.params, batch, self.source)

    def retune_buckets(self, n_buckets: int = 6,
                       warmup: bool = True) -> tuple:
        """Re-pick bucket boundaries from the observed batch-size histogram
        (ROADMAP: dynamic bucket tuning) and pre-compile the new shapes."""
        old = self.buckets
        self.buckets = tune_buckets(self.batch_sizes, self.max_batch,
                                    n_buckets)
        self.telemetry.emit("retune", version=self.source_version,
                            old_buckets=list(old),
                            new_buckets=list(self.buckets))
        if warmup:
            self.warmup()
        return self.buckets

    # -- request plumbing ---------------------------------------------------

    def submit(self, req: RecRequest):
        assert len(req.sparse_ids) == self.cfg.n_tables, \
            (len(req.sparse_ids), self.cfg.n_tables)
        with self.telemetry.span("enqueue", {"rid": req.rid}):
            if self._host_stores:
                self._req_streams(req)   # admission-time extraction
            self.batcher.submit(req)
        if self.telemetry.enabled:
            # live on enqueue, not only after a serve step — a stalled
            # serve loop must show its backlog, not the last drained value
            self._g_queue.set(len(self.batcher))

    def _assemble(self, reqs: List[RecRequest], bucket: int):
        """Pad a micro-batch to its bucket's static shapes.

        Returns ``(batch, n_valid)`` — n_valid is the real (unpadded)
        index count, computed host-side from the numpy offsets so the
        hit-rate probe never has to read a device array to learn it.
        """
        t = self.cfg.n_tables
        dense = np.zeros((bucket, self.cfg.dense_features), np.float32)
        for i, r in enumerate(reqs):
            dense[i] = r.dense
        if self.layout == "fixed":
            l = self.cfg.lookups_per_table
            idx = np.zeros((bucket, t, l), np.int32)
            for i, r in enumerate(reqs):
                for j, ids in enumerate(r.sparse_ids):
                    assert len(ids) == l, \
                        "fixed path requires exact-length bags"
                    idx[i, j] = ids
            # dummy rows gather row 0 — harmless, their outputs are dropped
            return {"dense": jnp.asarray(dense),
                    "indices": jnp.asarray(idx)}, 0
        lens = np.zeros(bucket * t, np.int32)
        for i, r in enumerate(reqs):
            for j, ids in enumerate(r.sparse_ids):
                assert len(ids) <= self.max_l, (len(ids), self.max_l)
                lens[i * t + j] = len(ids)
        offsets = np.zeros(bucket * t + 1, np.int32)
        np.cumsum(lens, out=offsets[1:])
        flat = np.zeros(bucket * t * self.max_l, np.int32)  # static cap
        for i, r in enumerate(reqs):
            for j, ids in enumerate(r.sparse_ids):
                o = offsets[i * t + j]
                flat[o:o + len(ids)] = ids
        return {"dense": jnp.asarray(dense),
                "indices": jnp.asarray(flat),
                "offsets": jnp.asarray(offsets)}, int(offsets[-1])

    def _dispatch_hit_probe(self, batch: Dict, n_valid: int) -> None:
        """Queue the per-batch hit-rate probe WITHOUT reading its result.

        The old accounting called float()/np.asarray() on the probe
        right here — a device sync on the serve hot path paid purely for
        bookkeeping. The futures now sit in ``_pending`` until a
        reporting boundary (stats / drain / swap) or the PENDING_MAX cap
        collects them, long after they completed.
        """
        if n_valid == 0:
            return
        if self.grouped:
            h, lk = self._hit_rate(self.source, batch["indices"],
                                   batch["offsets"])
            self._pending.append(("group", h, lk))
        elif self.cache is not None:
            hr = self._hit_rate(self.cache, batch["indices"],
                                batch["offsets"])
            self._pending.append(("cached", hr, n_valid))
        else:
            return
        if len(self._pending) >= self.PENDING_MAX:
            self._collect_pending()

    def _collect_pending(self) -> None:
        """Fold dispatched probe futures into the host-side counters."""
        if not self._pending:
            return
        pend, self._pending = self._pending, []
        for kind, a, b in pend:
            if kind == "group":
                self._hits += np.asarray(a, np.int64)
                self._lookups += np.asarray(b, np.int64)
            else:
                self._hits += float(a) * b
                self._lookups += b

    def _forward(self, batch: Dict, n_valid: int) -> np.ndarray:
        """One device forward; staged with per-stage device timing when
        the live Fig-5 mode is on."""
        tel = self.telemetry
        if self._staged is None:
            with tel.span("forward"):
                probs = np.asarray(self._run_serve(batch))
            if tel.enabled and self.layout != "fixed":
                self._dispatch_hit_probe(batch, n_valid)
            return probs
        sp, it, tp = self._staged
        reg = tel.registry
        with tel.span("sparse_lookup"):
            t0 = time.perf_counter()
            emb = sp(self.params, batch, self.source)
            emb.block_until_ready()
            t1 = time.perf_counter()
        with tel.span("interaction"):
            x = it(self.params, batch, emb)
            x.block_until_ready()
            t2 = time.perf_counter()
        with tel.span("mlp"):
            probs = np.asarray(tp(self.params, x))
            t3 = time.perf_counter()
        for name, dt in zip(_STAGE_NAMES, (t1 - t0, t2 - t1, t3 - t2)):
            reg.histogram("rec_stage_ms", "per-stage device time",
                          labels={"stage": name}).record(dt * 1e3)
        self._dispatch_hit_probe(batch, n_valid)
        return probs

    def step(self, force: bool = False) -> int:
        """Drain one micro-batch through the engine; returns #served."""
        tel = self.telemetry
        t_take0 = time.perf_counter()
        reqs = self.batcher.take(force=force)
        t_take1 = time.perf_counter()
        if not reqs:
            return 0
        # retune BEFORE the SLA clocks start: compiling the fresh bucket
        # shapes must not land on this micro-batch's recorded latency
        if self.auto_tune_after is not None and not self._retuned \
                and self._batches_seen >= self.auto_tune_after:
            self._retuned = True
            self.retune_buckets()
        now = time.time()
        now_m = time.monotonic()
        for r in reqs:
            r.started_at = now
            if tel.enabled:
                self._qwait_hist.record((now_m - r.submitted_mono) * 1e3)
        self._batches_seen += 1
        self._batch_ring.append(len(reqs))
        bucket = _bucket(len(reqs), self.buckets)
        self._warm.add(("primary", bucket))
        with tel.span("serve_step", {"batch_size": len(reqs),
                                     "bucket": bucket}):
            tel.tracer.record("batch", t_take0, t_take1)
            self._stage_batch(reqs)      # host-cold residency guarantee
            with tel.span("bucket_pad"):
                batch, n_valid = self._assemble(reqs, bucket)
            probs = self._forward(batch, n_valid)
            with tel.span("respond"):
                done = time.time()
                done_m = time.monotonic()
                for i, r in enumerate(reqs):
                    r.prob = float(probs[i])
                    r.finished_at = done
                    if tel.enabled:
                        # latency on the monotonic clock: an NTP step
                        # must not mint a negative (or week-long) p99
                        self._lat_hist.record((done_m - r.submitted_mono)
                                              * 1e3)
        self.served += len(reqs)
        if tel.enabled:
            self._c_served.inc(len(reqs))
            self._c_batches.inc()
            self._batch_hist.record(len(reqs))
            self._g_queue.set(len(self.batcher))
        return len(reqs)

    def drain(self) -> int:
        """Serve everything still queued (end-of-stream flush)."""
        n = 0
        while len(self.batcher):
            n += self.step(force=True)
        self._collect_pending()     # reporting boundary: settle accounting
        if self.telemetry.enabled:
            self._g_queue.set(len(self.batcher))
        self.telemetry.emit("drain", version=self.source_version,
                            served=n, queue_depth=len(self.batcher))
        return n

    # -- continuous batching: dispatch / settle -----------------------------

    def dispatch(self, reqs: List[RecRequest], *,
                 downgraded: bool = False) -> InflightBatch:
        """Assemble and dispatch one micro-batch WITHOUT settling it.

        The device-array result stays a future (no host conversion), so
        the caller can assemble the NEXT micro-batch while this one
        computes — continuous batching with in-flight refill, no wave
        barrier (``repro.serving.scheduler.SlaScheduler`` is the loop).
        ``downgraded=True`` serves from the int8 downgrade source
        (``enable_downgrade`` first) through the same jit — a different
        call-time pytree, its own warm compile-cache entry, no recompile.
        """
        assert reqs, "dispatch needs a non-empty micro-batch"
        assert self._staged is None, \
            ("device_stages (live Fig-5) syncs between stages — that "
             "defeats in-flight refill; characterize through step()")
        if downgraded:
            assert self._down_source is not None, \
                "call enable_downgrade() before dispatching a downgrade"
        tel = self.telemetry
        if self.auto_tune_after is not None and not self._retuned \
                and self._batches_seen >= self.auto_tune_after:
            self._retuned = True
            self.retune_buckets()
        now = time.time()
        now_m = time.monotonic()
        self._batches_seen += 1
        self._batch_ring.append(len(reqs))
        bucket = _bucket(len(reqs), self.buckets)
        kind = "downgrade" if downgraded else "primary"
        if tel.enabled and (kind, bucket) not in self._warm:
            self._c_cold.inc()
        self._warm.add((kind, bucket))
        for r in reqs:
            r.started_at = now
            r.downgraded = downgraded
            if tel.enabled:
                self._qwait_hist.record((now_m - r.submitted_mono) * 1e3)
        seq = next(self._dispatch_seq)
        with tel.span("dispatch", {"batch_size": len(reqs),
                                   "bucket": bucket, "path": kind,
                                   "batch": seq}):
            if not downgraded:
                self._stage_batch(reqs)  # host-cold residency guarantee
            with tel.span("bucket_pad"):
                batch, n_valid = self._assemble(reqs, bucket)
            if downgraded:
                probs = self._serve(self.params, batch, self._down_source)
            else:
                probs = self._run_serve(batch)
                if tel.enabled and self.layout != "fixed":
                    self._dispatch_hit_probe(batch, n_valid)
        return InflightBatch(reqs=reqs, probs=probs, bucket=bucket,
                             downgraded=downgraded, dispatched_mono=now_m,
                             seq=seq)

    def settle(self, ib: InflightBatch) -> int:
        """Block on an in-flight batch's device result and respond.

        The ``np.asarray`` here is the ONLY host sync of the
        dispatch/settle pair — by settle time the futures of a deep
        enough pipeline completed long ago, so it is a read, not a
        stall. Records end-to-end latency (monotonic) and the
        dispatch-to-settle service time per path. Traced, ``settle_wait``
        is the host blocked on the device and ``respond`` its own work.
        """
        tel = self.telemetry
        with tel.span("settle", {"batch_size": len(ib.reqs),
                                 "batch": ib.seq}):
            with tel.span("settle_wait"):
                probs = np.asarray(ib.probs)
            with tel.span("respond"):
                done = time.time()
                done_m = time.monotonic()
                for i, r in enumerate(ib.reqs):
                    r.prob = float(probs[i])
                    r.finished_at = done
                    if tel.enabled:
                        self._lat_hist.record(
                            (done_m - r.submitted_mono) * 1e3)
        self.served += len(ib.reqs)
        if tel.enabled:
            self._c_served.inc(len(ib.reqs))
            self._c_batches.inc()
            self._batch_hist.record(len(ib.reqs))
            tel.registry.histogram(
                "rec_service_ms", "dispatch-to-settle service time",
                labels={"path": "downgrade" if ib.downgraded
                        else "primary"}
            ).record((done_m - ib.dispatched_mono) * 1e3)
        return len(ib.reqs)

    # -- reporting ----------------------------------------------------------

    def live_fig5(self) -> Dict[str, float]:
        """The live Fig-5 characterization: mean per-stage device time
        and the embedding fraction, from real served traffic. Requires
        ``Telemetry(device_stages=True)``; comparable to the offline
        ``fig5_*`` rows in BENCH_paper.json."""
        assert self._staged is not None, \
            "live_fig5 needs Telemetry(device_stages=True)"
        reg = self.telemetry.registry
        means = {n: reg.histogram("rec_stage_ms",
                                  labels={"stage": n}).mean
                 for n in _STAGE_NAMES}
        total = sum(means.values())
        return {**{f"{n}_ms": means[n] for n in _STAGE_NAMES},
                "total_ms": total,
                "emb_frac": (means["sparse_lookup"] / total
                             if total else 0.0)}

    def stats(self) -> Dict[str, float]:
        self._collect_pending()     # reporting boundary: settle accounting
        h = self._lat_hist
        if h.count == 0:
            return {"n": 0}
        out = {"n": h.count,
               "path": self.path,
               "source": es.describe_source(self.source),
               # nested compositions one-per-line (the compact label above
               # is unreadable for deep/grouped sources)
               "source_tree": es.describe_source(self.source,
                                                 multiline=True),
               "p50_ms": h.percentile(50),
               "p95_ms": h.percentile(95),
               "p99_ms": h.percentile(99),
               "mean_ms": h.mean}
        # per-path-correct: None (not a fake 0.0) when no hot cache is
        # serving, or when no lookups have hit the live cache version yet
        if self.grouped:
            # per-table mapping; None preserved for non-cached members
            out["cache_hit_rate"] = {
                t: (float(self._hits[t] / self._lookups[t])
                    if self._lookups[t] else None)
                if es.hot_cache_of(m) is not None else None
                for t, m in enumerate(self.source.members)}
            out["cache_version"] = self.source_version
        elif self.cache is None:
            out["cache_hit_rate"] = None
        else:
            out["cache_hit_rate"] = (self._hits / self._lookups
                                     if self._lookups else None)
            out["cache_version"] = self.source_version
        out["buckets"] = self.buckets
        if self._host_stores:
            hs = [st.stats() for st, _ in self._host_stores]
            hits = sum(s["hits"] for s in hs)
            touches = sum(s["touches"] for s in hs)
            out["prefetch"] = {
                "hits": hits,
                "misses": sum(s["misses"] for s in hs),
                "touches": touches,
                "hit_rate": hits / touches if touches else 1.0,
                "staged_resident": sum(s["resident"] for s in hs),
                "host_bytes": sum(s["host_bytes"] for s in hs)}
        # windowed views (post-swap regressions must not average away):
        # since_swap restarts at every version bump, rolling covers the
        # last ring's worth of requests exactly
        out["since_swap"] = {"n": h.window_count,
                             "p50_ms": h.percentile(50, "window"),
                             "p95_ms": h.percentile(95, "window"),
                             "p99_ms": h.percentile(99, "window")}
        out["rolling"] = {"n": min(h.count, h.ring_size),
                          "p50_ms": h.percentile(50, "rolling"),
                          "p95_ms": h.percentile(95, "rolling"),
                          "p99_ms": h.percentile(99, "rolling")}
        if self._staged is not None:
            out["stages"] = self.live_fig5()
        return out


def requests_from_ragged_batch(batch: Dict[str, np.ndarray], n_tables: int,
                               rid0: int = 0) -> List[RecRequest]:
    """Explode a DLRMSynthetic.ragged_batch into individual requests."""
    off = batch["offsets"]
    b = (len(off) - 1) // n_tables
    out = []
    for i in range(b):
        ids = [batch["indices"][off[i * n_tables + j]:
                                off[i * n_tables + j + 1]]
               for j in range(n_tables)]
        out.append(RecRequest(rid=rid0 + i, dense=batch["dense"][i],
                              sparse_ids=ids))
    return out
