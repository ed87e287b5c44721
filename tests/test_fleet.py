"""Fleet chaos harness: fault-injected broadcast, exactness-asserted
recovery, crash consistency, and the determinism seams it leans on.

The property suite drives seeded drop/duplicate/delay/reorder schedules
(``repro.fleet.ChaosChannel``) between the online trainer's
``publish_source`` and replica ``RecEngine.update_source`` and asserts
the protocol invariants the fleet design claims:

(i)   every stale delivery the channel injects is rejected by the
      engine's version gate — ``stale_injected`` (channel side) equals
      the ``stale_rejected`` event count (engine side), exactly;
(ii)  after the chaos window, recovery within K clean version bumps is
      *bit-exact* against a trainer-synced reference engine — and takes
      zero new compile-cache entries (treedef-stable swaps);
(iii) per-version, per-model hit-rate attribution survives reordering:
      replicas only ever attribute traffic to versions the trainer
      actually published, monotonically.

Every scenario replays from its seed: no wall-clock randomness anywhere
in the chaos path: the pinned ``fleet`` hypothesis profile
(derandomized) keeps CI schedules reproducible.
"""
import os
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings
from hypothesis import strategies as st

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from repro import obs  # noqa: E402
from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs.dlrm import DLRM_SMOKE  # noqa: E402
from repro.core import dlrm  # noqa: E402
from repro.core import embedding_source as es  # noqa: E402
from repro.distributed.fault_tolerance import StragglerMonitor  # noqa: E402
from repro.fleet import CLEAN, ChaosChannel, FaultPlan, FleetRunner  # noqa: E402
from repro.fleet.runner import Replica, _serve_batch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.training.online import (OnlineCacheConfig, OnlineTrainer,  # noqa: E402
                                   OnlineGroupTrainer, _dense_head,
                                   make_drifting_zipf)

# Pinned hypothesis profile: CI runs HYPOTHESIS_PROFILE=fleet so chaos
# schedules are derandomized (replayable run to run).
settings.register_profile("fleet", deadline=None, max_examples=4,
                          derandomize=True, print_blob=True)
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "fleet"))

# The fault mixes the properties quantify over: lossy, duplicating, and
# heavily delaying (delay is what manufactures genuine reordering).
MIXES = (
    FaultPlan(drop=0.3, dup=0.3, delay=0.6, max_delay=3),
    FaultPlan(drop=0.0, dup=0.5, delay=0.8, max_delay=2),
)
# The fixed bench/demo plan (scanned so its schedule injects stale
# deliveries on every replica — reordering actually exercised).
BENCH_PLAN = FaultPlan(seed=6, drop=0.3, dup=0.3, delay=0.6, max_delay=3)


# ---------------------------------------------------------------------------
# ChaosChannel: the schedule is a pure function of (plan, send sequence)
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=8)
@given(seed=st.integers(min_value=0, max_value=1000),
       plan=st.sampled_from(MIXES))
def test_chaos_schedule_replays_from_seed(seed, plan):
    """Same plan seed + same send sequence => bit-identical fate
    transcript, counters, and delivery order — the replayability claim."""
    def run(chan):
        fates, delivered = [], []
        for v in range(1, 9):
            fates.append(chan.send(f"blob{v}".encode(), v))
            delivered += chan.poll()
        delivered += chan.flush()
        return fates, delivered, (chan.dropped, chan.duplicated,
                                  chan.delayed)

    p = plan.with_seed(seed)
    f1, d1, c1 = run(ChaosChannel(p))
    f2, d2, c2 = run(ChaosChannel(p))
    assert f1 == f2 and d1 == d2 and c1 == c2
    # conservation: every non-dropped copy is delivered exactly once
    n_copies = sum(0 if f["dropped"] else (2 if f["duplicated"] else 1)
                   for f in f1)
    assert len(d1) == n_copies
    # the transcript is complete and in send order
    assert [f["send"] for f in f1] == list(range(1, 9))
    drops = obs.Telemetry()
    chan = ChaosChannel(p, telemetry=drops)
    for v in range(1, 9):
        chan.send(f"blob{v}".encode(), v)
    assert len(drops.events.query("broadcast_dropped")) == chan.dropped


def test_chaos_clean_plan_is_perfect_transport():
    chan = ChaosChannel(CLEAN)
    for v in (1, 2, 3):
        chan.send(f"b{v}".encode(), v)
        got = chan.poll()
        assert [x[0] for x in got] == [v]
    assert chan.dropped == chan.duplicated == chan.delayed == 0
    assert chan.in_flight == 0


# ---------------------------------------------------------------------------
# (i)+(ii)+(iii): the grouped A/B fleet under chaos
# ---------------------------------------------------------------------------

def _assert_fleet_invariants(fr, published_versions):
    """The three chaos-suite assertions, shared by the property test and
    the deterministic bench-plan test."""
    # (i) channel-side injected staleness == engine-side rejections; the
    # reorder events carry the same count per replica
    for rep in fr.replicas:
        assert rep.stale_injected == rep.stale_rejections(), rep.name
        reordered = sum(
            len(e.telemetry.events.query("broadcast_reordered"))
            for e in rep.engines.values())
        assert reordered == rep.stale_injected, rep.name
        # both variant engines see identical delivery sequences
        v = rep.versions()
        assert v["a"] == v["b"], v

    # (ii) recovery to bit-exactness within K bumps, zero recompiles
    rec = fr.recover(k=3)
    assert all(all(flags) for flags in rec["exact"].values()), rec
    for per_model in rec["recompiles"]:
        for model, n in per_model.items():
            assert n in (0, None), (model, n)

    # (iii) attribution never invents versions: every version a replica
    # attributes traffic to was actually published by the trainer, and
    # rates are well-formed
    for rep in fr.replicas:
        for model in ("a", "b"):
            hrv = rep.hit_rate_by_version(model)
            # version 0 is the engine's initial pre-broadcast state (its
            # outgoing snapshot at the bootstrap swap); everything else
            # must be a version the trainer actually published
            assert set(hrv) <= set(published_versions) | {0}, (model, hrv)
            for rate in hrv.values():
                if rate is not None:
                    assert 0.0 <= rate <= 1.0, hrv


@settings(deadline=None, max_examples=4)
@given(seed=st.integers(min_value=3, max_value=6),
       plan=st.sampled_from(MIXES))
def test_fleet_chaos_property(seed, plan):
    """One trainer, two chaos-fed replicas, variants A/B over one shared
    TableGroupSource: protocol invariants hold for every seeded fault
    schedule."""
    fr = FleetRunner(n_replicas=2, plan=plan.with_seed(seed), seed=seed)
    for _ in range(3):
        fr.round()
    published = list(range(1, fr.trainer.version + 1))
    _assert_fleet_invariants(fr, published)


def test_fleet_bench_plan_injects_and_recovers():
    """The pinned bench plan (seed 6) actually produces reordering —
    stale injections are nonzero on every replica — and still recovers
    bit-exact. Also pins the A/B head semantics: variant B's dense head
    stays frozen through every broadcast; variant A's converges to the
    trainer's."""
    fr = FleetRunner(n_replicas=2, plan=BENCH_PLAN, seed=0)
    for _ in range(6):
        fr.round()
    assert all(rep.stale_injected > 0 for rep in fr.replicas), \
        [rep.stale_injected for rep in fr.replicas]
    _assert_fleet_invariants(fr, list(range(1, fr.trainer.version + 1)))

    want_b = jax.tree_util.tree_leaves(fr.head_b)
    want_a = jax.tree_util.tree_leaves(_dense_head(fr.trainer.params))
    for rep in fr.replicas:
        got_b = jax.tree_util.tree_leaves(
            _dense_head(rep.engines["b"].params))
        assert all(np.array_equal(g, w) for g, w in zip(got_b, want_b))
        got_a = jax.tree_util.tree_leaves(
            _dense_head(rep.engines["a"].params))
        assert all(np.array_equal(g, w) for g, w in zip(got_a, want_a))


# ---------------------------------------------------------------------------
# Sharded trainer, replicated replicas: shards in {1, 2, 4}
# ---------------------------------------------------------------------------

def _run_sharded_chaos(shards, seed=0):
    """A mesh-sharded OnlineTrainer broadcasts through a chaos channel to
    a replicated (meshless) replica; the clean-channel replica is the
    bit-exactness oracle. The blob round-trip is what makes the sharded
    and replicated worlds comparable: a replicated consumer deserializes
    the sharded artifact and the ShardedArena wrapper unwraps."""
    cfg = DLRM_SMOKE
    mesh = make_mesh((shards,), ("model",))
    max_l, B = 4, 8
    trainer = OnlineTrainer(
        cfg, dlrm.init(jax.random.PRNGKey(seed), cfg, shards),
        max_l=max_l, mesh=mesh,
        cache_cfg=OnlineCacheConfig(k=32, refresh_every=2))
    gen = make_drifting_zipf(cfg, batch_size=B, mean_l=2, max_l=max_l,
                             drift_per_batch=64, alpha=1.05, seed=seed)
    for _ in range(2):
        trainer.train_step(next(gen))
    vs0 = es.VersionedSource.deserialize(
        trainer.publish_source(include_head=True))
    assert isinstance(vs0.source.cold, es.FpArena)   # unwrapped for serving

    plan = FaultPlan(seed=seed + 11 * shards, drop=0.3, dup=0.3,
                     delay=0.6, max_delay=3)
    rep = Replica("replica0", cfg, vs0, ChaosChannel(plan), max_l=max_l,
                  batch_size=B, heads={"a": dict(vs0.head)},
                  params_seed=seed + 2, shards=shards)
    ref = Replica("ref", cfg, vs0, ChaosChannel(CLEAN), max_l=max_l,
                  batch_size=B, heads={"a": dict(vs0.head)},
                  params_seed=seed + 5, shards=shards)
    probe = next(gen)
    for _ in range(4):
        for _ in range(2):
            trainer.train_step(next(gen))
        blob = trainer.publish_source(include_head=True)
        ref.deliver(trainer.version, blob)
        rep.channel.send(blob, trainer.version)
        rep.pump()

    # (i) holds shard-independently
    assert rep.stale_injected == rep.stale_rejections()

    # drain in-flight, then one clean republish closes any dropped tail
    for v, blob in rep.channel.flush():
        rep.deliver(v, blob)
    blob = trainer.publish_source(include_head=True)
    rep.deliver(trainer.version, blob)

    # (ii) bit-exact vs the trainer source (via the clean-channel oracle)
    got = _serve_batch(rep.engines["a"], cfg, probe)
    want = _serve_batch(ref.engines["a"], cfg, probe)
    assert got == want
    assert rep.recompiles()["a"] in (0, None)
    assert rep.versions()["a"] == trainer.version
    return rep


@settings(deadline=None, max_examples=3)
@given(shards=st.sampled_from([1, 2, 4]))
def test_fleet_chaos_sharded(shards):
    if shards > jax.device_count():
        return      # single-device job covers shards=1; CI multidevice
        #             job (8 forced host devices) covers 2 and 4
    _run_sharded_chaos(shards)


# ---------------------------------------------------------------------------
# Crash scenarios: replica restart + trainer crash/resume
# ---------------------------------------------------------------------------

def test_replica_restart_restores_from_checkpoint(tmp_path):
    """Kill a replica mid-chaos; its replacement bootstraps from the
    latest checkpointed source artifact, emits ``replica_restore``, and
    recovers to bit-exact within K bumps with zero recompiles."""
    fr = FleetRunner(n_replicas=2, plan=BENCH_PLAN, seed=1,
                     ckpt_dir=tmp_path)
    for _ in range(2):
        fr.round()
    rep = fr.crash_replica(0)
    restores = [e for eng in rep.engines.values()
                for e in eng.telemetry.events.query("replica_restore")]
    assert len(restores) == len(rep.engines)
    # the restart bootstrapped from the newest persisted artifact
    vs, manifest = fr.ckpt.restore_source()
    assert all(e.version == vs.version for e in restores)
    assert all(e.attrs["step"] == manifest["step"] for e in restores)
    rec = fr.recover(k=3)
    assert all(all(flags) for flags in rec["exact"].values()), rec
    for per_model in rec["recompiles"]:
        assert all(n in (0, None) for n in per_model.values())


def test_trainer_crash_resume_data_skip_determinism(tmp_path):
    """ResilientTrainer + CheckpointManager through a mid-run crash: the
    resumed trainer's params are BIT-IDENTICAL to an uninterrupted
    control trainer fed the same step-seeded batches (data-skip
    determinism), the version stays monotone (replicas never see a
    rollback), and the fleet recovers to exactness afterwards."""
    fr = FleetRunner(n_replicas=1, plan=BENCH_PLAN.with_seed(2), seed=2,
                     ckpt_dir=tmp_path)
    fr.round()
    v_before = fr.trainer.version
    res = fr.run_trainer_with_crash(extra_steps=6, fail_after=3,
                                    ckpt_every=2)
    assert res["restarts"] == 1
    assert res["resume_events"] == 1
    assert res["version"] >= v_before      # monotone through the crash

    # control: same init seed, same memoized batch stream, no crash
    ctl = OnlineGroupTrainer(
        fr.cfg, dlrm.init(jax.random.PRNGKey(fr.seed), fr.cfg),
        max_l=fr.max_l, plans=dlrm.table_plans(fr.cfg, cache_k=64),
        refresh_every=fr.trainer.refresh_every)
    for step in range(fr.next_step):
        ctl.train_step(fr.batch_fn(step))
    got = jax.tree_util.tree_leaves(fr.trainer.params)
    want = jax.tree_util.tree_leaves(ctl.params)
    assert len(got) == len(want)
    assert all(np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in zip(got, want))

    rec = fr.recover(k=3)
    assert all(all(flags) for flags in rec["exact"].values()), rec


# ---------------------------------------------------------------------------
# Checkpoint crash consistency (satellite): a writer dying mid-publish
# ---------------------------------------------------------------------------

def test_checkpoint_crash_consistency(tmp_path, monkeypatch):
    """Kill the writer between tmp-write and atomic rename: the latest
    PRIOR step must restore intact, and the orphaned ``tmp.<step>``
    debris must be GC'd by the next successful save."""
    ckpt = CheckpointManager(tmp_path, keep_n=3)
    ckpt.save(1, {"w": np.arange(4.0)})
    ckpt.save(2, {"w": np.arange(4.0) + 1})

    real_rename = Path.rename
    die = {"on": True}

    def dying_rename(self, target):
        if die["on"] and self.name.startswith("tmp."):
            raise OSError("writer killed mid-publish")
        return real_rename(self, target)

    monkeypatch.setattr(Path, "rename", dying_rename)
    with pytest.raises(OSError):
        ckpt.save(3, {"w": np.arange(4.0) + 2})

    # crash-consistent: the torn write is invisible to restore
    assert ckpt.latest_step() == 2
    state, manifest = ckpt.restore({"w": np.zeros(4)})
    assert manifest["step"] == 2
    assert np.array_equal(np.asarray(state["w"]), np.arange(4.0) + 1)
    assert any(p.name == "tmp.3" for p in tmp_path.iterdir())

    # the next successful save sweeps the debris
    die["on"] = False
    ckpt.save(4, {"w": np.arange(4.0) + 3})
    assert not list(tmp_path.glob("tmp.*"))
    assert ckpt.latest_step() == 4

    # same protocol for source artifacts (tmp.src.<step>)
    arena = jnp.arange(32.0, dtype=jnp.float32).reshape(8, 4)
    ckpt.save_source(5, es.VersionedSource(source=es.FpArena(arena),
                                           version=1))
    die["on"] = True
    with pytest.raises(OSError):
        ckpt.save_source(6, es.VersionedSource(source=es.FpArena(arena + 1),
                                               version=2))
    vs, manifest = ckpt.restore_source()
    assert manifest["step"] == 5 and vs.version == 1
    assert any(p.name == "tmp.src.6" for p in tmp_path.iterdir())
    die["on"] = False
    ckpt.save_source(7, es.VersionedSource(source=es.FpArena(arena + 2),
                                           version=3))
    assert not list(tmp_path.glob("tmp.*"))
    assert ckpt.latest_source_step() == 7
    vs, _ = ckpt.restore_source()
    assert np.array_equal(np.asarray(vs.source.arena), np.asarray(arena + 2))


# ---------------------------------------------------------------------------
# Loadgen determinism (satellite): the open-loop trace is seed-pure
# ---------------------------------------------------------------------------

def test_loadgen_trace_deterministic_from_seed():
    from benchmarks.loadgen import make_trace
    cfg = DLRM_SMOKE
    t1 = make_trace(cfg, 96, kind="poisson", rate_qps=500.0, mean_l=3,
                    max_l=6, drift_per_chunk=200, seed=11)
    t2 = make_trace(cfg, 96, kind="poisson", rate_qps=500.0, mean_l=3,
                    max_l=6, drift_per_chunk=200, seed=11)
    assert np.array_equal(t1.arrivals_s, t2.arrivals_s)
    assert len(t1.requests) == len(t2.requests) == 96
    for a, b in zip(t1.requests, t2.requests):
        assert a.rid == b.rid
        assert np.array_equal(a.dense, b.dense)
        assert len(a.sparse_ids) == len(b.sparse_ids)
        assert all(np.array_equal(x, y)
                   for x, y in zip(a.sparse_ids, b.sparse_ids))
    # a different seed actually changes the trace (the test would pass
    # vacuously if make_trace ignored its seed)
    t3 = make_trace(cfg, 96, kind="poisson", rate_qps=500.0, mean_l=3,
                    max_l=6, drift_per_chunk=200, seed=12)
    assert not np.array_equal(t1.arrivals_s, t3.arrivals_s)


def test_loadgen_drift_moves_hot_set_across_chunks():
    from benchmarks.loadgen import zipf_requests

    def hot_row(reqs):
        ids = np.concatenate([i for r in reqs for i in r.sparse_ids])
        vals, counts = np.unique(ids, return_counts=True)
        return int(vals[np.argmax(counts)])

    cfg = DLRM_SMOKE
    drift = zipf_requests(cfg, 128, mean_l=6, max_l=12, alpha=1.05,
                          drift_per_chunk=350, chunk=64, seed=4)
    hot0, hot1 = hot_row(drift[:64]), hot_row(drift[64:])
    assert hot0 != hot1
    # rank 1 maps to row shift % rows: the drift is a rotation
    assert hot1 == (hot0 + 350) % cfg.rows_per_table

    flat = zipf_requests(cfg, 128, mean_l=6, max_l=12, alpha=1.05,
                         drift_per_chunk=0, chunk=64, seed=4)
    assert hot_row(flat[:64]) == hot_row(flat[64:])


# ---------------------------------------------------------------------------
# StragglerMonitor window-pollution regression (satellite)
# ---------------------------------------------------------------------------

def test_straggler_monitor_consecutive_stragglers_all_flagged():
    """Flagged outliers must stay out of the median window: a run of
    consecutive stragglers previously raised the median enough to mask
    the next one of comparable magnitude."""
    mon = StragglerMonitor(threshold=2.0, window=8)
    for step in range(8):
        assert not mon.record(step, 1.0)
    # four consecutive 3x stragglers, then a 5x one: with window
    # pollution the 3.0s shift the median to 3.0 and the 5.0 sails
    # under the 2*median bar; with the fix the median stays 1.0
    for step, dt in enumerate([3.0, 3.0, 3.0, 3.0, 5.0], start=8):
        assert mon.record(step, dt), (step, dt)
    assert len(mon.events) == 5
    assert all(e["median"] == 1.0 for e in mon.events)
    assert set(mon.durations) == {1.0}      # window never polluted


def test_straggler_monitor_two_back_to_back(rng):
    """The minimal regression: two immediately consecutive stragglers
    are both flagged."""
    mon = StragglerMonitor(threshold=2.0, window=8)
    for step in range(8):
        mon.record(step, 1.0)
    assert mon.record(8, 10.0)
    assert mon.record(9, 10.0)
    assert len(mon.events) == 2
