"""Telemetry subsystem: bounded streaming metrics, span tracing, the
swap event log, and their wiring through the serving engine.

The contracts pinned here are the ones the obs redesign sold:

* histogram percentiles are EXACT (vs np.percentile) while the stream
  fits the raw ring, and bucket-bounded afterwards;
* stats() keeps its pre-obs keys and the None-not-0.0 hit-rate rule,
  now from O(1)-memory instruments;
* the hit-rate probe never syncs on the serve hot path — futures are
  converted only at reporting boundaries (pinned with a conversion-spy
  proxy);
* disabled telemetry is genuinely free: the stage hooks return one
  shared null context and the compiled HLO is op-for-op identical with
  annotations on vs off;
* swap events attribute the outgoing version's hit rate
  (hit_rate_by_version), and the since-swap latency window restarts.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.dlrm import DLRM_SMOKE
from repro.core import dlrm
from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.data import DLRMSynthetic
from repro.launch import hlo_analysis
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.tracing import Tracer, _NULL
from repro.serving import RecEngine, requests_from_ragged_batch

MAX_L = 6


@pytest.fixture
def setup():
    cfg = DLRM_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    data = DLRMSynthetic(cfg, seed=9)
    return cfg, params, data


def _make_engine(cfg, params, data, *, source="cached", telemetry=None):
    rb = data.ragged_batch(8, dist="poisson", mean_l=3, max_l=MAX_L)
    spec = dlrm.arena_spec(cfg)
    counts = se.trace_row_counts(spec, rb["indices"], rb["offsets"])
    kw = ({"cache_k": 32, "cache_trace": counts}
          if source == "cached" else {})
    return RecEngine(cfg, params, source=source, max_l=MAX_L,
                     max_batch=4, max_wait_ms=0.0, buckets=(4,),
                     telemetry=telemetry, **kw)


def _serve(engine, data, n=8, seed=None):
    d = data if seed is None else DLRMSynthetic(engine.cfg, seed=seed)
    rb = d.ragged_batch(n, dist="poisson", mean_l=3, max_l=MAX_L)
    reqs = requests_from_ragged_batch(rb, engine.cfg.n_tables,
                                      rid0=engine.served)
    for r in reqs:
        engine.submit(r)
    engine.drain()
    return reqs


# ---------------------------------------------------------------------------
# histograms: exact-while-small, bounded-error forever
# ---------------------------------------------------------------------------

def test_histogram_exact_percentiles_while_stream_fits_ring():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=1.0, sigma=1.0, size=500)
    h = Histogram("t", ring=2048)
    for v in vals:
        h.record(v)
    for q in (50, 90, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)), rel=0, abs=0)
    assert h.count == 500
    assert h.total == pytest.approx(vals.sum())


def test_histogram_bucket_estimate_error_bounded_by_growth():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(mean=1.5, sigma=0.8, size=5000)
    h = Histogram("t", growth=1.08, ring=64)     # 5000 >> ring: estimates
    for v in vals:
        h.record(v)
    for q in (50, 95, 99):
        true = float(np.percentile(vals, q))
        est = h.percentile(q)
        assert abs(est - true) / true <= 0.09, (q, est, true)


def test_histogram_window_and_rolling_views():
    h = Histogram("t", growth=1.08, ring=32)
    for v in np.linspace(1.0, 2.0, 100):
        h.record(v)
    h.reset_window()
    assert h.window_count == 0
    fast = np.linspace(10.0, 20.0, 50)
    for v in fast:
        h.record(v)
    # window sees ONLY the post-reset (10x slower) samples
    assert h.percentile(50, "window") > 5.0
    assert h.window_count == 50
    # rolling = exact over the last ring-full of raw samples
    assert h.percentile(50, "rolling") == pytest.approx(
        float(np.percentile(fast[-32:], 50)))
    # cumulative keeps everything
    assert h.count == 150


def test_histogram_out_of_range_clamps_instead_of_growing():
    h = Histogram("t", lo=1.0, hi=100.0, ring=8)
    for v in (1e-9, 0.5, 1e6):
        h.record(v)
    assert h.count == 3
    assert h._counts.sum() == 3          # every sample landed in a bucket


def test_histogram_fraction_leq_matches_empirical():
    rng = np.random.default_rng(2)
    vals = rng.uniform(1.0, 10.0, size=200)
    h = Histogram("t", ring=2048)
    for v in vals:
        h.record(v)
    for cut in (2.0, 5.0, 9.0):
        assert h.fraction_leq(cut) == pytest.approx(
            float(np.mean(vals <= cut)))


def test_counter_monotone_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(AssertionError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3.0


def test_registry_get_or_create_and_label_families():
    reg = MetricsRegistry()
    a = reg.histogram("stage_ms", labels={"stage": "emb"})
    b = reg.histogram("stage_ms", labels={"stage": "emb"})
    c = reg.histogram("stage_ms", labels={"stage": "mlp"})
    assert a is b and a is not c
    fam = reg.histograms("stage_ms")
    assert set(fam) == {'stage_ms{stage="emb"}', 'stage_ms{stage="mlp"}'}


def test_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", {"path": "cached"}).inc(3)
    reg.gauge("ver", "version").set(2)
    h = reg.histogram("lat_ms", "latency", lo=1.0, hi=100.0, growth=2.0,
                      ring=8)
    for v in (1.0, 2.0, 4.0):
        h.record(v)
    assert reg.exposition() == """\
# HELP req_total requests
# TYPE req_total counter
req_total{path="cached"} 3
# HELP ver version
# TYPE ver gauge
ver 2
# HELP lat_ms latency
# TYPE lat_ms summary
lat_ms{quantile="0.5"} 2
lat_ms{quantile="0.95"} 3.8
lat_ms{quantile="0.99"} 3.96
lat_ms_sum 7
lat_ms_count 3
"""


def test_registry_snapshot_is_jsonable():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.histogram("h").record(1.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["c"] == 1
    assert snap["histograms"]["h"]["count"] == 1


# ---------------------------------------------------------------------------
# tracing: nesting, bounds, the free disabled path
# ---------------------------------------------------------------------------

def test_span_nesting_assigns_parent_and_trace():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    inner, inner2, outer = tr.spans()
    assert [s.name for s in (inner, inner2, outer)] == \
        ["inner", "inner2", "outer"]         # children finish first
    assert inner.parent_id == outer.span_id
    assert inner2.parent_id == outer.span_id
    assert inner.trace_id == inner2.trace_id == outer.trace_id
    assert outer.parent_id is None
    with tr.span("next"):
        pass
    assert tr.spans("next")[0].trace_id != outer.trace_id


def test_tracer_record_nests_pretimed_span_under_open_span():
    tr = Tracer()
    with tr.span("step"):
        s = tr.record("pre", 1.0, 2.0)
    step = tr.spans("step")[0]
    assert s.parent_id == step.span_id
    assert s.duration_ms == pytest.approx(1000.0)


def test_tracer_memory_is_bounded():
    """The ring keeps the newest spans and counts what it drops."""
    tr = Tracer(max_spans=8)
    for _ in range(40):
        with tr.span("s"):
            pass
    tr.record("r", 0.0, 1.0)
    assert len(tr.spans()) == 8 and tr.dropped == 33
    tr.clear()
    with tr.span("s"):
        pass
    assert tr.dropped == 0


def test_disabled_tracer_and_stage_return_shared_null():
    tr = Tracer(enabled=False)
    assert tr.span("x") is tr.span("y") is _NULL
    assert tr.record("x", 0.0, 1.0) is None and not tr.spans()
    # the jit-side hook: one module-level singleton, no allocation
    assert not obs.stage_annotations_enabled()
    assert obs.stage("sparse_lookup") is obs.stage("mlp") is _NULL
    assert obs.step_annotation(3) is _NULL


def test_stage_annotations_leave_compiled_ops_identical(setup):
    """Flipping annotations on must change metadata only: the op
    histograms of the compiled ragged serve step and sparse train step
    are identical, and the scopes below the stages reach the op names."""
    cfg, params, data = setup
    rb = data.ragged_batch(4, dist="poisson", mean_l=3, max_l=MAX_L,
                           pad_to=4 * cfg.n_tables * MAX_L)
    batch = {"dense": jnp.asarray(rb["dense"]),
             "indices": jnp.asarray(rb["indices"]),
             "offsets": jnp.asarray(rb["offsets"])}
    src = es.FpArena(params["arena"])
    train_batch = dict(batch, labels=jnp.asarray(rb["labels"]))

    def compiled():
        # fresh step functions, so JAX cannot reuse an earlier trace
        step = dlrm.make_ragged_serve_step(cfg, max_l=MAX_L)
        opt, train = dlrm.make_train_step_ragged(cfg, max_l=MAX_L,
                                                 lr=1e-2)
        return (
            jax.jit(step).lower(params, batch, src).compile().as_text(),
            jax.jit(train).lower(params, opt.init(params), train_batch)
            .compile().as_text())

    assert not obs.stage_annotations_enabled()
    off = compiled()
    obs.enable_stage_annotations(True)
    try:
        on = compiled()
    finally:
        obs.enable_stage_annotations(False)
    assert [hlo_analysis.count_ops(t) for t in on] == \
        [hlo_analysis.count_ops(t) for t in off]
    serve_on, train_on = on
    for scope in ("relayout", "gather_reduce"):
        assert f"/emb_lookup/{scope}/" in serve_on
        assert f"/emb_lookup/{scope}/" in train_on
    for scope in ("sparse_update", "dense_update"):
        assert f"/{scope}/" in train_on
    assert not any(f"/{scope}/" in t for t in off for scope in
                   ("relayout", "gather_reduce", "sparse_update",
                    "dense_update"))


def test_enabled_spans_land_on_the_profiler_trace(tmp_path):
    """An enabled span is also a host event of a jax.profiler trace, on
    the device's clock, with its scalar attrs as stats; a disabled
    tracer adds nothing."""
    from jax.profiler import ProfileData

    tr, off = Tracer(), Tracer(enabled=False)
    assert off.span("hidden") is _NULL
    x = jnp.ones(3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("outer", {"batch": 7, "arr": [1]}):
            with tr.span("inner"), off.span("hidden"):
                (x + 1).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    events = {ev.name: ev
              for plane in ProfileData.from_file(str(pb)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert "hidden" not in events
    outer, inner = events["outer"], events["inner"]
    assert dict(outer.stats) == {"batch": 7}
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= \
        outer.start_ns + outer.duration_ns
    assert [s.name for s in tr.spans() if s.name != "compile"] == \
        ["inner", "outer"]


def test_compiles_are_attributed_to_the_innermost_open_span():
    from jax._src import monitoring

    from repro.obs import tracing

    tel = obs.Telemetry(tracing=True)
    tr = tel.tracer
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones(17)
    with tel.span("outer"):
        with tel.span("step"):
            f(x).block_until_ready()                # compiles
        f(x).block_until_ready()                    # cached: no compile
    (c,) = tr.spans("compile")
    (step,) = tr.spans("step")
    assert c.parent_id == step.span_id and c.attrs["span"] == "step"
    assert step.start <= c.start <= c.end <= step.end
    counters = tel.registry.snapshot()["counters"]
    assert counters == {**counters, 'compiles_total{span="step"}': 1.0}
    assert 'compiles_total{span="outer"}' not in counters
    # a disabled tracer records nothing; the listener is there only
    # while some enabled tracer is
    tr.enabled = False
    with tel.span("step"):
        jax.jit(lambda x: x - 2.0)(x).block_until_ready()
    assert len(tr.spans("compile")) == 1
    listening = tracing._on_compile in \
        monitoring.get_event_duration_listeners()
    assert listening == bool(list(tracing._WATCHED))
    assert tr not in list(tracing._WATCHED)


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_event_log_hit_rate_attribution():
    log = obs.EventLog()
    log.emit("source_swap", version=2, prev_version=1, hits=30.0,
             lookups=40.0)
    log.emit("cache_swap", version=3, prev_version=2, hits=0.0,
             lookups=0.0)                      # served no traffic
    log.emit("hot_cache_rebuild", version=3, k=64)   # not a swap: ignored
    rates = log.hit_rate_by_version()
    assert rates == {1: 0.75, 2: None}
    assert len(log.query("cache_swap")) == 1
    assert log.query(version=3)[0].kind == "cache_swap"
    for line in log.to_jsonl().splitlines():
        json.loads(line)


def test_event_log_is_bounded():
    log = obs.EventLog(max_events=4)
    for i in range(10):
        log.emit("publish", version=i)
    assert len(log) == 4
    assert [e.version for e in log.events] == [6, 7, 8, 9]


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_stats_keeps_compat_keys_and_adds_windows(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data)
    _serve(engine, data)
    st = engine.stats()
    # the pre-obs surface, unchanged
    for key in ("n", "path", "source", "p50_ms", "p95_ms", "p99_ms",
                "mean_ms", "cache_hit_rate", "cache_version", "buckets"):
        assert key in st, key
    assert st["n"] == 8
    assert 0.0 <= st["cache_hit_rate"] <= 1.0
    assert st["p50_ms"] <= st["p95_ms"] <= st["p99_ms"]
    # exact percentiles while the ring holds the stream: the histogram
    # must agree with the raw per-request latencies
    lats_ms = np.asarray(engine.latencies) * 1e3
    assert st["p50_ms"] == pytest.approx(float(np.percentile(lats_ms, 50)))
    # the new windowed views
    assert st["since_swap"]["n"] == 8
    assert st["rolling"]["n"] == 8
    # ring-backed compatibility properties stay lists
    assert len(engine.latencies) == 8
    assert engine.batch_sizes == [4, 4]


def test_engine_hit_rate_is_none_not_zero_without_cache(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data, source="ragged")
    _serve(engine, data)
    assert engine.stats()["cache_hit_rate"] is None


def test_engine_swap_attributes_outgoing_version_and_resets_window(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data)
    spec = dlrm.arena_spec(cfg)
    _serve(engine, data)
    rb = data.ragged_batch(8, dist="poisson", mean_l=3, max_l=MAX_L)
    counts = se.trace_row_counts(spec, rb["indices"], rb["offsets"])
    fresh = se.build_hot_cache(params["arena"], spec, counts, 32)
    engine.update_cache(fresh, version=1)

    (ev,) = engine.telemetry.events.query("cache_swap")
    assert ev.version == 1 and ev.attrs["prev_version"] == 0
    assert ev.attrs["lookups"] > 0
    rate = engine.telemetry.events.hit_rate_by_version()[0]
    assert rate is not None and 0.0 <= rate <= 1.0
    # counters reset with the version; the since-swap window restarts
    # while cumulative history stays
    assert engine._lookups == 0
    st = engine.stats()
    assert st["since_swap"]["n"] == 0 and st["n"] == 8
    assert st["cache_hit_rate"] is None        # no lookups on v1 yet
    # the --metrics-json body carries it all, JSON-able
    snap = json.loads(json.dumps(engine.telemetry.snapshot(), default=str))
    assert snap["hit_rate_by_version"]["0"] == pytest.approx(rate)
    assert any(e["kind"] == "cache_swap" for e in snap["events"])


def test_engine_stale_swap_rejected_with_event(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data)
    spec = dlrm.arena_spec(cfg)
    rb = data.ragged_batch(4, dist="poisson", mean_l=3, max_l=MAX_L)
    counts = se.trace_row_counts(spec, rb["indices"], rb["offsets"])
    fresh = se.build_hot_cache(params["arena"], spec, counts, 32)
    engine.update_cache(fresh, version=5)
    with pytest.raises(ValueError, match="stale"):
        engine.update_cache(fresh, version=3)
    (ev,) = engine.telemetry.events.query("stale_rejected")
    assert ev.version == 3 and ev.attrs["served_version"] == 5
    reg = engine.telemetry.registry
    assert reg.counter("rec_stale_rejected_total").value == 1
    assert reg.gauge("rec_source_version").value == 5


class _ConversionSpy:
    """Stands in for the hit-rate probe's device future: records whether
    anything host-converted it (the sync the hot path must not pay)."""

    def __init__(self):
        self.converted = False

    def __float__(self):
        self.converted = True
        return 0.5


def test_hit_probe_defers_host_conversion_to_reporting_boundary(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data)
    spies = []

    def fake_probe(cache, idx, off):
        spies.append(_ConversionSpy())
        return spies[-1]

    engine._hit_rate = fake_probe
    _serve(engine, data)                      # 2 micro-batches
    assert len(spies) == 2
    assert len(engine._pending) == 0          # drain() is a boundary
    assert all(s.converted for s in spies)

    # steps alone (no boundary) must NOT convert
    rb = data.ragged_batch(4, dist="poisson", mean_l=3, max_l=MAX_L)
    for r in requests_from_ragged_batch(rb, cfg.n_tables, rid0=100):
        engine.submit(r)
    engine.step(force=True)
    assert not spies[-1].converted and len(engine._pending) == 1
    engine.stats()                            # reporting boundary
    assert spies[-1].converted and not engine._pending


def test_hit_probe_pending_cap_collects_in_bulk(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data)
    engine.PENDING_MAX = 3
    spies = []

    def fake_probe(cache, idx, off):
        spies.append(_ConversionSpy())
        return spies[-1]

    engine._hit_rate = fake_probe
    for i in range(3):
        rb = data.ragged_batch(4, dist="poisson", mean_l=3, max_l=MAX_L)
        for r in requests_from_ragged_batch(rb, cfg.n_tables,
                                            rid0=100 * i):
            engine.submit(r)
        engine.step(force=True)
    # third dispatch hit the cap: everything collected, queue empty
    assert all(s.converted for s in spies) and not engine._pending


def test_disabled_telemetry_serves_uninstrumented(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data,
                          telemetry=obs.Telemetry.disabled())
    reqs = _serve(engine, data)
    assert all(r.prob is not None for r in reqs)     # still serves
    assert engine.stats() == {"n": 0}
    assert engine.latencies == []
    assert engine._lookups == 0 and not engine._pending
    assert not engine.telemetry.tracer.spans()
    spec = dlrm.arena_spec(cfg)
    rb = data.ragged_batch(4, dist="poisson", mean_l=3, max_l=MAX_L)
    counts = se.trace_row_counts(spec, rb["indices"], rb["offsets"])
    engine.update_cache(
        se.build_hot_cache(params["arena"], spec, counts, 32), version=1)
    assert len(engine.telemetry.events) == 0         # emit is a no-op


def test_engine_spans_cover_the_serving_pipeline(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data,
                          telemetry=obs.Telemetry(tracing=True))
    _serve(engine, data, n=4)                        # one micro-batch
    tr = engine.telemetry.tracer
    (step,) = tr.spans("serve_step")
    assert step.attrs == {"batch_size": 4, "bucket": 4}
    children = {s.name for s in tr.spans()
                if s.parent_id == step.span_id}
    # the unwarmed engine compiles its hit probe inside this first step
    assert children == {"batch", "bucket_pad", "forward", "respond",
                        "compile"}
    assert len(tr.spans("enqueue")) == 4             # one per submit


def test_retune_emits_event_and_batch_ring_is_bounded(setup):
    cfg, params, data = setup
    engine = _make_engine(cfg, params, data)
    assert engine._batch_ring.maxlen == 1024         # auto-tune cap
    _serve(engine, data)
    engine.retune_buckets(warmup=False)
    (ev,) = engine.telemetry.events.query("retune")
    assert ev.attrs["old_buckets"] == [4]
    assert ev.attrs["new_buckets"] == list(engine.buckets)


def test_device_stages_match_fused_and_report_live_fig5(setup):
    cfg, params, data = setup
    fused = _make_engine(cfg, params, data)
    staged = _make_engine(cfg, params, data,
                          telemetry=obs.Telemetry(device_stages=True))
    r_f = _serve(fused, data, seed=21)
    r_s = _serve(staged, data, seed=21)
    np.testing.assert_allclose([r.prob for r in r_s],
                               [r.prob for r in r_f], rtol=1e-5,
                               atol=1e-6)
    fig5 = staged.live_fig5()
    assert set(fig5) == {"sparse_lookup_ms", "interaction_ms", "mlp_ms",
                         "total_ms", "emb_frac"}
    assert 0.0 < fig5["emb_frac"] < 1.0
    assert fig5["total_ms"] == pytest.approx(
        fig5["sparse_lookup_ms"] + fig5["interaction_ms"]
        + fig5["mlp_ms"])
    assert staged.stats()["stages"] == staged.live_fig5()
    # 3 labeled stage histograms, 2 batches each
    fam = staged.telemetry.registry.histograms("rec_stage_ms")
    assert len(fam) == 3
    assert all(h.count == 2 for h in fam.values())


def test_fixed_layout_rejects_device_stages(setup):
    cfg, params, _ = setup
    with pytest.raises(AssertionError, match="device_stages"):
        RecEngine(cfg, params, source="fixed",
                  telemetry=obs.Telemetry(device_stages=True))


# ---------------------------------------------------------------------------
# trainer events
# ---------------------------------------------------------------------------

def test_online_trainer_emits_rebuild_events_and_metrics():
    from repro.training import OnlineCacheConfig, OnlineTrainer

    cfg = DLRM_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    data = DLRMSynthetic(cfg, seed=3)
    tel = obs.Telemetry()
    trainer = OnlineTrainer(cfg, params, max_l=MAX_L, lr=1e-2,
                            cache_cfg=OnlineCacheConfig(k=32,
                                                        refresh_every=4),
                            telemetry=tel)
    pad = 16 * cfg.n_tables * MAX_L
    for _ in range(9):
        trainer.train_step(data.ragged_batch(16, dist="poisson", mean_l=3,
                                             max_l=MAX_L, pad_to=pad))
    rebuilds = tel.events.query("hot_cache_rebuild")
    assert len(rebuilds) == 2                        # steps 4 and 8
    assert rebuilds[-1].version == trainer.version
    assert rebuilds[-1].attrs["k"] == 32
    reg = tel.registry
    assert reg.counter("train_steps_total").value == 9
    assert reg.counter("train_rebuilds_total").value == 2
    assert reg.gauge("train_cache_version").value == trainer.version
    assert reg.gauge("train_loss").value == pytest.approx(
        trainer.losses[-1])


def _children(tr, span):
    return [s.name for s in sorted(tr.spans(), key=lambda s: s.start)
            if s.parent_id == span.span_id]


def test_online_trainer_step_spans(tmp_path):
    """Traced, a training step is train_step -> feed, step, rows,
    loss_sync, in that order, and its profiler event is a step marker."""
    from jax.profiler import ProfileData

    from repro.training import OnlineTrainer

    cfg = DLRM_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    data = DLRMSynthetic(cfg, seed=3)
    tel = obs.Telemetry(tracing=True)
    trainer = OnlineTrainer(cfg, params, max_l=MAX_L, lr=1e-2,
                            telemetry=tel)
    pad = 16 * cfg.n_tables * MAX_L
    batches = [data.ragged_batch(16, dist="poisson", mean_l=3, max_l=MAX_L,
                                 pad_to=pad) for _ in range(3)]
    trainer.train_step(batches[0])
    jax.profiler.start_trace(str(tmp_path))
    try:
        for b in batches[1:]:
            trainer.train_step(b)
    finally:
        jax.profiler.stop_trace()
    tr = tel.tracer
    steps = tr.spans("train_step")
    assert [s.attrs for s in steps] == [{}, {}, {}]
    for st in steps:
        assert _children(tr, st) == ["feed", "step", "rows", "loss_sync"]
    # the first step compiled inside its ``step`` span
    (c,) = tr.spans("compile")
    assert c.parent_id == tr.spans("step")[0].span_id
    (pb,) = tmp_path.rglob("*.xplane.pb")
    marks = [dict(ev.stats).get("step_num")
             for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for ev in line.events
             if ev.name == "train_step"]
    assert sorted(marks) == [2, 3]


def test_online_group_trainer_step_spans():
    from repro.configs.dlrm import DLRM_HET_SMOKE
    from repro.training import OnlineGroupTrainer

    cfg = DLRM_HET_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    tel = obs.Telemetry(tracing=True)
    tr = OnlineGroupTrainer(cfg, params, max_l=MAX_L,
                            plans=dlrm.table_plans(cfg), lr=1e-2,
                            telemetry=tel)
    data = DLRMSynthetic(cfg, seed=5)
    for _ in range(2):
        tr.train_step(data.ragged_batch(4, dist="poisson", mean_l=3,
                                        max_l=MAX_L,
                                        pad_to=4 * cfg.n_tables * MAX_L))
    steps = tel.tracer.spans("train_step")
    assert len(steps) == 2
    for st in steps:
        assert _children(tel.tracer, st) == \
            ["feed", "step", "rows", "loss_sync"]
