"""Batcher/scheduler admission invariants (the serving plane).

Property tests (hypothesis) for the open-loop scheduling machinery:

* the batcher's wait deadline runs on the MONOTONIC clock — no request
  waits past its deadline, and a wall-clock (NTP) step can neither
  flush a batch early nor stall it;
* ``plan_batch`` is deterministic given (queue state, SLA) and its
  shed/serve/downgrade split respects FIFO and the deadline;
* the scheduler's ledger balances at every step — submitted ==
  served + shed + queued + in-flight, drained count equals enqueued
  count, and every shed request is accounted for by exactly one event;
* FIFO order within a bucket is preserved across in-flight refills.

The properties run against a deterministic fake engine + fake clock
(no device, no wall time); a small end-to-end section exercises the
real ``RecEngine`` dispatch/settle path, the int8 downgrade source,
and the warm compile-cache pool.
"""
import time

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import obs
from repro.configs.dlrm import DLRM_SMOKE
from repro.core import dlrm
from repro.data import DLRMSynthetic
from repro.serving import (InflightBatch, RecBatcher, RecEngine,
                           RecRequest, ServiceEstimator, SlaPolicy,
                           SlaScheduler, plan_batch,
                           requests_from_ragged_batch)
from repro.serving.rec_engine import _bucket


class FakeClock:
    """A monotonic clock the test advances by hand (seconds)."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def _req(rid, clock, n_tables=2):
    return RecRequest(rid=rid,
                      dense=np.zeros(2, np.float32),
                      sparse_ids=[np.zeros(1, np.int32)] * n_tables,
                      submitted_mono=clock())


class FakeEngine:
    """The narrow engine surface ``SlaScheduler`` drives, with service
    time modeled on the fake clock: ``settle`` advances it by
    ``service_s`` (the device 'finishing' the batch)."""

    layout = "ragged"

    def __init__(self, clock, service_s=0.004, max_batch=8,
                 buckets=(2, 8), telemetry=None):
        self.clock = clock
        self.service_s = service_s
        self.max_batch = max_batch
        self.buckets = tuple(buckets)
        self.telemetry = telemetry if telemetry is not None \
            else obs.Telemetry()
        self.source_version = 0
        self.downgrade_source = None
        self.dispatched = []            # [(rids tuple, downgraded)]

    def enable_downgrade(self):
        self.downgrade_source = object()
        return self.downgrade_source

    def dispatch(self, reqs, *, downgraded=False):
        self.dispatched.append((tuple(r.rid for r in reqs), downgraded))
        for r in reqs:
            r.downgraded = downgraded
        return InflightBatch(reqs=list(reqs), probs=None,
                             bucket=_bucket(len(reqs), self.buckets),
                             downgraded=downgraded,
                             dispatched_mono=self.clock())

    def settle(self, ib):
        done = max(ib.dispatched_mono + self.service_s, self.clock())
        self.clock.t = done
        for r in ib.reqs:
            r.prob = 0.5
            r.finished_at = time.time()
        return len(ib.reqs)

    def _collect_pending(self):
        pass


def make_sched(clock, policy, **engine_kw):
    eng = FakeEngine(clock, **engine_kw)
    return eng, SlaScheduler(eng, policy, clock=clock)


# ---------------------------------------------------------------------------
# RecBatcher: monotonic wait deadlines
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(st.integers(1, 50), st.integers(1, 5))
def test_batcher_deadline_on_monotonic_clock(wait_ms, n):
    """No queued request waits past max_wait_ms on the monotonic clock:
    the batch is held strictly inside the budget and released the
    instant the oldest request's wait reaches it."""
    clock = FakeClock()
    b = RecBatcher(max_batch=100, max_wait_ms=wait_ms, clock=clock)
    for i in range(n):
        b.submit(_req(i, clock))
    t0 = clock()
    clock.advance(wait_ms * 1e-3 * 0.99)
    assert b.take() == []            # inside the budget: held
    clock.t = t0 + wait_ms * 1e-3 * 1.001
    out = b.take()
    assert [r.rid for r in out] == list(range(n))   # at the deadline
    assert len(b) == 0


def test_batcher_immune_to_wall_clock_steps(monkeypatch):
    """An NTP wall-clock step must neither flush a batch early nor
    stall it past max_wait_ms (the old deadline math ran on
    time.time() against submitted_at and did both)."""
    clock = FakeClock()
    b = RecBatcher(max_batch=100, max_wait_ms=10.0, clock=clock)
    req = _req(0, clock)
    req.submitted_at = time.time()
    b.submit(req)
    # wall clock leaps a day forward: still inside the monotonic budget
    monkeypatch.setattr(time, "time", lambda: req.submitted_at + 86400.0)
    assert b.take() == []
    # wall clock leaps backward, monotonic deadline passes: released
    monkeypatch.setattr(time, "time", lambda: req.submitted_at - 86400.0)
    clock.advance(0.011)
    assert [r.rid for r in b.take()] == [0]


def test_batcher_releases_full_batch_regardless_of_clock():
    clock = FakeClock()
    b = RecBatcher(max_batch=2, max_wait_ms=1e9, clock=clock)
    b.submit(_req(0, clock))
    assert b.take() == []
    b.submit(_req(1, clock))
    assert len(b.take()) == 2        # full batch: no wait needed


# ---------------------------------------------------------------------------
# plan_batch: pure, deterministic, FIFO- and deadline-respecting
# ---------------------------------------------------------------------------

_POLICY_STRATEGY = dict(
    sla=st.integers(1, 100),
    shed_margin=st.sampled_from([1.0, 1.5]),
    downgrade_margin=st.sampled_from([0.5, 1.0]),
    allow_shed=st.booleans(),
    allow_downgrade=st.booleans(),
    est_full=st.integers(1, 50),
    est_cheap=st.integers(1, 50),
    inflight=st.integers(0, 100),
)


@settings(deadline=None, max_examples=60)
@given(waits=st.lists(st.integers(0, 200), min_size=0, max_size=12),
       slots=st.integers(1, 8), **_POLICY_STRATEGY)
def test_plan_batch_deterministic_and_invariant(
        waits, slots, sla, shed_margin, downgrade_margin, allow_shed,
        allow_downgrade, est_full, est_cheap, inflight):
    """Shed/downgrade decisions are a deterministic function of (queue
    state, SLA): same inputs -> same plan; sheds are exactly the
    hopeless FIFO prefix; the admitted head makes the shed deadline."""
    waits = sorted([float(w) for w in waits], reverse=True)  # FIFO: head oldest
    policy = SlaPolicy(sla_ms=float(sla), shed_margin=shed_margin,
                       downgrade_margin=downgrade_margin,
                       allow_shed=allow_shed,
                       allow_downgrade=allow_downgrade)
    kw = dict(slots=slots, policy=policy, est_full_ms=float(est_full),
              est_cheap_ms=float(est_cheap), inflight_ms=float(inflight))
    plan = plan_batch(waits, **kw)
    assert plan == plan_batch(waits, **kw)          # deterministic
    assert 0 <= plan.shed <= len(waits)
    assert 0 <= plan.serve <= min(slots, len(waits) - plan.shed)
    assert plan.shed + plan.serve <= len(waits)
    if not allow_shed:
        assert plan.shed == 0
    if not allow_downgrade:
        assert not plan.downgraded
    deadline = policy.sla_ms * policy.shed_margin
    cheapest = (min(est_full, est_cheap) if allow_downgrade else est_full)
    # sheds are exactly the hopeless prefix — FIFO is never reordered
    for i in range(plan.shed):
        assert waits[i] + inflight + cheapest > deadline
    if allow_shed and plan.serve > 0:
        assert waits[plan.shed] + inflight + cheapest <= deadline
        # the admitted head's prediction makes the deadline (guaranteed
        # when the downgrade escape hatch sits below the shed margin)
        if downgrade_margin <= shed_margin:
            assert plan.predicted_ms <= deadline + 1e-9


def test_plan_batch_downgrades_only_when_cheaper():
    policy = SlaPolicy(sla_ms=10.0, downgrade_margin=0.5)
    kw = dict(slots=4, policy=policy, inflight_ms=0.0)
    # full path would cross the margin and int8 is cheaper: downgrade
    plan = plan_batch([2.0], est_full_ms=8.0, est_cheap_ms=4.0, **kw)
    assert plan.downgraded and plan.predicted_ms == 6.0
    # int8 not actually cheaper (CPU-style estimate): never downgrade
    plan = plan_batch([2.0], est_full_ms=8.0, est_cheap_ms=8.0, **kw)
    assert not plan.downgraded
    # comfortably under the margin: serve full precision
    plan = plan_batch([0.0], est_full_ms=3.0, est_cheap_ms=1.0, **kw)
    assert not plan.downgraded


def test_service_estimator_is_deterministic_and_falls_back():
    a, b = ServiceEstimator(default_ms=7.0), ServiceEstimator(default_ms=7.0)
    assert a.estimate("primary", 8) == 7.0          # cold prior
    assert a.estimate("downgrade", 8) == 7.0        # borrows primary
    for est in (a, b):
        est.observe("primary", 8, 4.0)
        est.observe("primary", 8, 2.0)
        est.observe("downgrade", 2, 1.0)
    assert a.estimate("primary", 8) == b.estimate("primary", 8)
    assert a.estimate("primary", 2) == a.estimate("primary", 8)  # nearest
    assert a.estimate("downgrade", 8) == 1.0        # nearest observed


# ---------------------------------------------------------------------------
# SlaScheduler: ledger balance, FIFO across refills, shed accounting
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(bursts=st.lists(st.integers(0, 6), min_size=1, max_size=8),
       sla=st.sampled_from([2, 20, 1000]),
       allow_downgrade=st.booleans())
def test_scheduler_ledger_balances_at_every_step(bursts, sla,
                                                 allow_downgrade):
    """submitted == served + shed + queued + inflight at every point;
    after drain the queue and pipeline are empty and the drained count
    equals the enqueued count (minus accounted sheds)."""
    clock = FakeClock()
    eng, sched = make_sched(clock, SlaPolicy(
        sla_ms=float(sla), allow_downgrade=allow_downgrade,
        max_queue=16, default_service_ms=4.0))
    rid = 0

    def balanced():
        assert sched.submitted == (sched.served + sched.shed
                                   + len(sched._queue) + sched.inflight)

    for burst in bursts:
        for _ in range(burst):
            sched.submit(_req(rid, clock))
            rid += 1
            clock.advance(0.001)
            balanced()
        sched.pump()
        balanced()
    drained = sched.drain()
    balanced()
    assert len(sched._queue) == 0 and sched.inflight == 0
    assert sched.submitted == rid
    assert sched.served + sched.shed == rid        # drained == enqueued
    assert drained <= sched.served
    # every shed request carries exactly one shed event + the flag
    shed_events = [e for e in sched.telemetry.events.events
                   if e.kind == "shed"]
    assert len(shed_events) == sched.shed
    assert int(sched._c_shed.value) == sched.shed
    # and the final drain event closes the ledger
    drain_ev = [e for e in sched.telemetry.events.events
                if e.kind == "drain"][-1]
    assert drain_ev.attrs["served"] == sched.served
    assert drain_ev.attrs["shed"] == sched.shed


def test_scheduler_fifo_preserved_across_refills():
    """Requests are dispatched in strict rid order even while earlier
    batches are still in flight (refill never reorders the queue)."""
    clock = FakeClock()
    eng, sched = make_sched(clock, SlaPolicy(
        sla_ms=1e6, allow_shed=False, allow_downgrade=False),
        max_batch=4)
    rid = 0
    for _ in range(6):                  # bursts interleaved with pumps
        for _ in range(3):
            sched.submit(_req(rid, clock))
            rid += 1
        clock.advance(0.002)
        sched.pump()
    sched.drain()
    order = [r for rids, _ in eng.dispatched for r in rids]
    assert order == sorted(order) == list(range(rid))
    assert sched.served == rid and sched.shed == 0
    # refills actually happened (batches dispatched behind in-flight ones)
    assert int(sched._c_refill.value) > 0


def test_scheduler_sheds_hopeless_and_downgrades_under_pressure():
    clock = FakeClock()
    eng, sched = make_sched(clock, SlaPolicy(
        sla_ms=10.0, downgrade_margin=0.5, default_service_ms=4.0))
    # teach the estimator the int8 path is cheaper (as calibration would)
    sched.estimator.observe("primary", 8, 4.0)
    sched.estimator.observe("downgrade", 8, 2.0)
    sched.estimator.observe("primary", 2, 4.0)
    sched.estimator.observe("downgrade", 2, 2.0)
    stale = _req(0, clock)
    sched.submit(stale)
    clock.advance(0.020)                # 20ms > sla: hopeless
    fresh = [_req(i, clock) for i in range(1, 9)]
    for r in fresh:
        sched.submit(r)
    clock.advance(0.004)                # 4ms + full 4ms > 5ms margin
    sched.pump()
    sched.drain()
    assert stale.shed and stale.prob is None
    assert sched.shed == 1 and sched.served == 8
    assert sched.downgraded == 8       # pressure picked the int8 path
    assert all(r.downgraded for r in fresh)
    kinds = [e.kind for e in sched.telemetry.events.events]
    assert kinds.count("shed") == 1 and "downgrade" in kinds


def test_scheduler_hard_queue_cap_sheds_at_submit():
    clock = FakeClock()
    eng, sched = make_sched(clock, SlaPolicy(sla_ms=1e6, max_queue=4))
    accepted = [sched.submit(_req(i, clock)) for i in range(7)]
    assert accepted == [True] * 4 + [False] * 3
    assert sched.shed == 3
    reasons = [e.attrs["reason"] for e in sched.telemetry.events.events
               if e.kind == "shed"]
    assert reasons == ["queue_full"] * 3
    sched.drain()
    assert sched.served == 4


# ---------------------------------------------------------------------------
# Real engine end-to-end: dispatch/settle, downgrade source, warm pool
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_engine():
    cfg = DLRM_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    data = DLRMSynthetic(cfg, seed=9)
    rb = data.ragged_batch(24, dist="poisson", mean_l=3, max_l=6)
    reqs = requests_from_ragged_batch(rb, cfg.n_tables)
    eng = RecEngine(cfg, params, source="ragged", max_l=6, max_batch=8,
                    buckets=(2, 8), telemetry=obs.Telemetry())
    return cfg, eng, reqs


def test_engine_dispatch_settle_matches_step_path(served_engine):
    cfg, eng, reqs = served_engine
    eng.enable_downgrade()
    eng.warmup()
    assert eng._c_cold.value == 0
    batch = reqs[:8]
    ib = eng.dispatch(batch)
    assert [r.rid for r in ib.reqs] == [r.rid for r in batch]
    assert eng.settle(ib) == 8
    full = [r.prob for r in batch]
    assert all(p is not None for p in full)
    # the downgrade path serves the same requests within int8 error,
    # through the SAME jit (different call-time pytree)
    ib = eng.dispatch(batch, downgraded=True)
    assert eng.settle(ib) == 8
    down = [r.prob for r in batch]
    assert all(r.downgraded for r in batch)
    np.testing.assert_allclose(down, full, atol=0.05)
    # warm pool: both paths on both buckets were compiled by warmup,
    # so no dispatch above paid a cold compile
    assert {("primary", 2), ("primary", 8),
            ("downgrade", 2), ("downgrade", 8)} <= eng._warm
    assert eng._c_cold.value == 0
    # latency and queue-wait are recorded per request, on monotonic time
    assert eng._qwait_hist.count >= 16
    assert all(v >= 0 for v in eng._lat_hist.ring_values())


def test_cold_compile_counter_trips_without_warmup():
    cfg = DLRM_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    eng = RecEngine(cfg, params, source="ragged", max_l=6, max_batch=8,
                    buckets=(8,))
    data = DLRMSynthetic(cfg, seed=9)
    reqs = requests_from_ragged_batch(
        data.ragged_batch(8, dist="poisson", mean_l=3, max_l=6),
        cfg.n_tables)
    eng.settle(eng.dispatch(reqs))
    assert eng._c_cold.value == 1      # unwarmed bucket paid its compile


def test_queue_depth_gauge_live_and_drain_event(served_engine):
    cfg, _, reqs = served_engine
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    eng = RecEngine(cfg, params, source="ragged", max_l=6, max_batch=8,
                    buckets=(8,))
    for i, r in enumerate(reqs[:5]):
        eng.submit(r)
        assert eng._g_queue.value == i + 1      # live on enqueue
    eng.drain()
    assert eng._g_queue.value == 0              # true depth after drain
    drain_ev = [e for e in eng.telemetry.events.events
                if e.kind == "drain"]
    assert drain_ev and drain_ev[-1].attrs["served"] == 5
    assert drain_ev[-1].attrs["queue_depth"] == 0


def test_scheduler_end_to_end_on_real_engine(served_engine):
    cfg, _, _ = served_engine
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    data = DLRMSynthetic(cfg, seed=3)
    reqs = requests_from_ragged_batch(
        data.ragged_batch(32, dist="poisson", mean_l=3, max_l=6),
        cfg.n_tables)
    eng = RecEngine(cfg, params, source="ragged", max_l=6, max_batch=8,
                    buckets=(2, 8))
    sched = SlaScheduler(eng, SlaPolicy(sla_ms=250.0, max_queue=64))
    sched.warmup()
    for r in reqs:
        r.submitted_mono = time.monotonic()
        sched.submit(r)
        sched.pump()
    sched.drain()
    assert sched.submitted == 32
    assert sched.served + sched.shed == 32
    for r in reqs:
        assert (r.prob is not None) != r.shed    # served XOR shed
    shed_events = [e for e in eng.telemetry.events.events
                   if e.kind == "shed"]
    assert len(shed_events) == sched.shed
    s = sched.stats()
    assert s["submitted"] == 32 and 0.0 <= s["shed_frac"] <= 1.0
    if sched.served:
        assert s["n"] == sched.served


def test_scheduler_turn_spans_nest_and_join_batches(served_engine):
    """Traced, each turn is pump -> plan / dispatch / settle, settle
    splits into settle_wait (blocked on the device) and respond, and a
    batch's dispatch and settle spans carry the same ``batch``."""
    cfg, _, reqs = served_engine
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    eng = RecEngine(cfg, params, source="ragged", max_l=6, max_batch=8,
                    buckets=(2, 8), telemetry=obs.Telemetry(tracing=True))
    sched = SlaScheduler(eng, SlaPolicy(allow_shed=False,
                                        allow_downgrade=False))
    sched.warmup(calibrate=False)
    for r in reqs:
        r.submitted_mono = time.monotonic()
        sched.submit(r)
        sched.pump()
    while sched.inflight:
        sched.pump()
    tr = eng.telemetry.tracer
    by_id = {s.span_id: s for s in tr.spans()}

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id else None

    assert {parent(s) for s in tr.spans("plan")} == {"pump"}
    assert {parent(s) for s in tr.spans("dispatch")} == {"pump"}
    assert {parent(s) for s in tr.spans("settle")} == {"pump"}
    assert {parent(s) for s in tr.spans("bucket_pad")} == {"dispatch"}
    assert {parent(s) for s in tr.spans("settle_wait")} == {"settle"}
    assert {parent(s) for s in tr.spans("respond")} == {"settle"}
    assert all(s.attrs["queued"] >= 1 for s in tr.spans("plan"))
    dispatched = {s.attrs["batch"]: s for s in tr.spans("dispatch")}
    settled = {s.attrs["batch"]: s for s in tr.spans("settle")}
    assert dispatched.keys() == settled.keys()
    assert len(dispatched) == len(tr.spans("dispatch")) >= 3
    for k, d in dispatched.items():
        assert d.end <= settled[k].start
        assert d.attrs["batch_size"] == settled[k].attrs["batch_size"]
    assert tr.dropped == 0
