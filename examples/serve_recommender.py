"""End-to-end driver: serve personalized-recommendation traffic — the
paper's deployment scenario (Section IV-A: user-facing inference with firm
SLAs) over the ragged production sparse path.

Request stream (variable bag lengths, Zipfian row skew)
    -> RecBatcher admission (SLA micro-batching)
    -> RecEngine bucket-padded DLRM inference
       (--path fixed | ragged | cached; cached pins the top-K hottest rows)
    -> CTR predictions + per-request latency percentiles.

    PYTHONPATH=src python examples/serve_recommender.py \
        [--requests 4096] [--path cached] [--cache-k 4096]

With ``--replicas N`` (N >= 2) the driver instead demonstrates the
multi-host cache-coherence protocol: one online trainer keeps learning and
periodically publishes its versioned hot arena as ONE serialized broadcast
artifact; N serving replicas deserialize and adopt it atomically (stale
re-deliveries are rejected at the engine boundary), and every replica's
predictions stay exactly equal to the uncached forward on the live params.

    PYTHONPATH=src python examples/serve_recommender.py \
        --replicas 2 --online-steps 60 --cache-k 512

With ``--het`` the driver serves a heterogeneous TABLE GROUP instead:
per-table vocab/dim/skew, per-table composition (hot-cache the skewed
tables, int8 the big one), online per-table refresh under one group-wide
version, and per-table hit rates in stats().

    PYTHONPATH=src python examples/serve_recommender.py --het

With ``--fleet`` the driver runs the chaos-hardened fleet scenario: one
group trainer broadcasting full source+head ``VersionedSource`` blobs to
N replicas serving TWO model variants (A/B) over one shared table group;
``--chaos`` injects seeded drop/duplicate/delay/reorder faults on every
replica's channel, and recovery is asserted bit-exact against a
trainer-synced reference within 3 clean version bumps (zero recompiles).

    PYTHONPATH=src python examples/serve_recommender.py \
        --fleet --chaos --replicas 2 --online-steps 24

With ``--open-loop`` the driver switches from the closed-loop wave above
to OPEN-LOOP arrivals (requests come on their own Poisson/diurnal clock
and do not wait for the server) served by the SLA-aware continuous
batcher (``repro.serving.scheduler``): in-flight refill, overload
shedding, int8 downgrade under pressure. ``--qps 0`` calibrates the
offered rate from the engine's measured capacity times ``--overload``.

    PYTHONPATH=src python examples/serve_recommender.py \
        --open-loop --requests 2000 --overload 2.0 --arrivals poisson

Telemetry (``repro.obs``): ``--metrics-json FILE`` dumps the registry
snapshot + swap events at exit, ``--trace`` collects per-request spans
and turns on the jax.profiler stage annotations, and ``--live-fig5``
serves through the per-stage device-timed pipeline and prints the
paper's Fig-5 embedding-vs-MLP split measured on this very traffic.

    PYTHONPATH=src python examples/serve_recommender.py \
        --requests 512 --path cached --live-fig5 --metrics-json /tmp/m.json
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.dlrm import DLRM_CONFIGS, DLRM_SMOKE
from repro.core import dlrm
from repro.core import sparse_engine as se
from repro.data import DLRMSynthetic
from repro.launch.compile_cache import use_compile_cache
from repro.serving import RecEngine, requests_from_ragged_batch


def _make_telemetry(args) -> obs.Telemetry:
    if args.trace:
        obs.enable_stage_annotations(True)
    return obs.Telemetry(tracing=args.trace,
                         device_stages=args.live_fig5)


def _finish_telemetry(args, telemetry: obs.Telemetry) -> None:
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(telemetry.snapshot(), f, indent=2, default=str)
        print(f"metrics snapshot -> {args.metrics_json}")
    if args.trace:
        spans = telemetry.tracer.spans("serve_step")
        if spans:
            ms = np.asarray([sp.duration_ms for sp in spans])
            print(f"traced {len(spans)} serve_step spans "
                  f"(p50 {np.percentile(ms, 50):.2f} ms); last trace:")
            last = [sp for sp in telemetry.tracer.spans()
                    if sp.trace_id == spans[-1].trace_id]
            for sp in last:
                print(f"  {sp.name:<14} {sp.duration_ms:8.3f} ms")


def serve_once(args) -> None:
    """Single-engine SLA serving run (the original driver)."""
    if args.sla_ms is None:
        args.sla_ms = 10.0
    cfg = DLRM_CONFIGS["dlrm1"]
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    data = DLRMSynthetic(cfg, seed=7)
    dist = "fixed" if args.path == "fixed" else args.dist
    max_l = cfg.lookups_per_table if dist == "fixed" \
        else 2 * cfg.lookups_per_table

    # The cached path profiles a warmup trace first (top-K by frequency).
    cache_trace = None
    if args.path == "cached":
        warm = data.ragged_batch(4096, dist=dist, max_l=max_l)
        cache_trace = se.trace_row_counts(dlrm.arena_spec(cfg),
                                          warm["indices"], warm["offsets"])

    cached = args.path == "cached"
    telemetry = _make_telemetry(args)
    engine = RecEngine(cfg, params, source=args.path, max_l=max_l,
                       max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms,
                       cache_k=args.cache_k if cached else 0,
                       cache_trace=cache_trace,
                       quantize_cold=args.quantize_cold and cached,
                       telemetry=telemetry)

    # Compile every bucket shape off the clock.
    engine.warmup()

    t0 = time.perf_counter()
    rid = 0
    while rid < args.requests:
        n = min(args.max_batch, args.requests - rid)
        for r in requests_from_ragged_batch(
                data.ragged_batch(n, dist=dist, max_l=max_l),
                cfg.n_tables, rid0=rid):
            engine.submit(r)
        rid += n
        engine.step()
    engine.drain()
    wall = time.perf_counter() - t0

    s = engine.stats()
    # the streaming histogram answers the SLA-attainment query directly —
    # no unbounded per-request latency list anywhere in the engine
    sla_frac = telemetry.registry.histogram(
        "rec_request_latency_ms").fraction_leq(args.sla_ms)
    print(f"served {s['n']} requests on the '{args.path}' path "
          f"(bag lengths: {dist}, max_l={max_l})")
    print(f"latency per request: p50 {s['p50_ms']:.2f} ms  "
          f"p95 {s['p95_ms']:.2f} ms  p99 {s['p99_ms']:.2f} ms")
    print(f"throughput: {s['n'] / wall:.0f} req/s")
    print(f"SLA ({args.sla_ms:.0f} ms): "
          f"{100.0 * sla_frac:.1f}% of requests in budget")
    if s.get("cache_hit_rate") is not None:   # None on non-cached sources
        print(f"hot-row cache: K={args.cache_k}, "
              f"hit rate {100.0 * s['cache_hit_rate']:.1f}%")
    if args.live_fig5:
        f5 = engine.live_fig5()
        print(f"live Fig-5 (per-stage device time, this traffic): "
              f"emb {f5['sparse_lookup_ms']:.2f} ms | interact "
              f"{f5['interaction_ms']:.2f} ms | top-MLP "
              f"{f5['mlp_ms']:.2f} ms -> emb_frac "
              f"{f5['emb_frac']:.2f}")
    _finish_telemetry(args, telemetry)


def serve_open_loop(args) -> None:
    """Open-loop arrivals through the SLA-aware continuous batcher."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks import loadgen

    from repro.serving import SlaPolicy, SlaScheduler

    cfg = DLRM_CONFIGS["dlrm1"]
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    max_l = 2 * cfg.lookups_per_table
    telemetry = _make_telemetry(args)
    engine = RecEngine(cfg, params, source=args.path, max_l=max_l,
                       max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
                       buckets=(args.max_batch // 4, args.max_batch),
                       telemetry=telemetry)

    # calibrate capacity: settled full batches, telemetry off so the
    # warm-up compile and the stale calibration stamps never pollute the
    # served-traffic histograms / counters
    data = DLRMSynthetic(cfg, seed=7)
    cal = requests_from_ragged_batch(
        data.ragged_batch(args.max_batch, dist="poisson", max_l=max_l),
        cfg.n_tables)
    engine.telemetry = obs.Telemetry.disabled()
    engine.settle(engine.dispatch(cal))
    t0 = time.perf_counter()
    for _ in range(5):
        engine.settle(engine.dispatch(cal))
    t_batch = (time.perf_counter() - t0) / 5
    engine.telemetry = telemetry
    capacity_qps = args.max_batch / t_batch
    rate_qps = args.qps or capacity_qps * args.overload
    sla_ms = args.sla_ms if args.sla_ms is not None else 3 * t_batch * 1e3

    sched = SlaScheduler(engine, SlaPolicy(
        sla_ms=sla_ms, default_service_ms=t_batch * 1e3,
        max_queue=4 * args.max_batch))
    sched.warmup()                       # warm pool + service calibration

    trace = loadgen.make_trace(
        cfg, args.requests, kind=args.arrivals, rate_qps=rate_qps,
        mean_l=cfg.lookups_per_table, max_l=max_l, drift_per_chunk=64)
    print(f"open-loop {args.arrivals} arrivals: offered "
          f"{trace.offered_qps:.0f} qps vs capacity {capacity_qps:.0f} qps "
          f"({trace.offered_qps / capacity_qps:.1f}x), SLA {sla_ms:.2f} ms")
    wall = loadgen.replay(trace, sched.submit, sched.pump)
    sched.drain()

    s = sched.stats()
    print(f"submitted {s['submitted']}: served {s['served']}, "
          f"shed {s['shed']} ({100 * s['shed_frac']:.1f}%), "
          f"downgraded {s['downgraded']} "
          f"({100 * s['downgrade_frac']:.1f}%)")
    if s.get("n"):
        print(f"latency per served request: p50 {s['p50_ms']:.2f} ms  "
              f"p99 {s['p99_ms']:.2f} ms (SLA {sla_ms:.2f} ms)")
    if "queue_wait_p99_ms" in s:
        print(f"queue wait: p50 {s['queue_wait_p50_ms']:.2f} ms  "
              f"p99 {s['queue_wait_p99_ms']:.2f} ms")
    print(f"goodput: {s['served'] / wall:.0f} req/s over {wall:.2f} s; "
          f"cold compiles after warmup: "
          f"{int(telemetry.registry.counter('rec_cold_compiles_total').value)}")
    _finish_telemetry(args, telemetry)


def serve_broadcast_fleet(args) -> None:
    """Trainer + N serving replicas under the versioned-broadcast protocol."""
    from repro.training import (OnlineCacheConfig, OnlineTrainer,
                                VersionedHotCache, make_drifting_zipf)

    cfg = DLRM_SMOKE
    spec = dlrm.arena_spec(cfg)
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    max_l = 2 * cfg.lookups_per_table
    k = min(args.cache_k, spec.null_row)

    trainer = OnlineTrainer(
        cfg, params, max_l=max_l,
        cache_cfg=OnlineCacheConfig(k=k, refresh_every=args.cache_refresh))
    gen = make_drifting_zipf(cfg, batch_size=16, mean_l=3, max_l=max_l,
                             drift_per_batch=3)
    trainer.train_step(next(gen))
    trainer.rebuild_cache()                      # version 1 exists up front

    data = DLRMSynthetic(cfg, seed=23)
    replicas = []
    for i in range(args.replicas):
        eng = RecEngine(cfg, trainer.params, source="cached", max_l=max_l,
                        max_batch=8, max_wait_ms=0.0, cache_k=k,
                        cache_trace=trainer.hist)
        blob = trainer.publish()
        VersionedHotCache.deserialize(blob).apply(eng)
        replicas.append(eng)

    rounds = max(1, args.online_steps // args.cache_refresh)
    print(f"fleet: 1 trainer -> {args.replicas} replicas, "
          f"K={k}, refresh every {args.cache_refresh} steps")
    for rnd in range(rounds):
        for _ in range(args.cache_refresh):
            trainer.train_step(next(gen))
        blob = trainer.publish()                 # ONE artifact, N consumers
        art = VersionedHotCache.deserialize(blob)
        for eng in replicas:
            eng.params = trainer.params          # param + cache pair swap
            adopted = art.apply(eng)
            assert adopted or eng.cache_version >= art.version

        # replicas must agree with each other AND with the uncached
        # forward over the live params — the protocol's whole point
        rb = data.ragged_batch(6, mean_l=3, max_l=max_l)
        probs = []
        for eng in replicas:
            reqs = requests_from_ragged_batch(rb, cfg.n_tables)
            for r in reqs:
                eng.submit(r)
            eng.step(force=True)
            probs.append(np.asarray([r.prob for r in reqs]))
        want = np.asarray(jax.nn.sigmoid(dlrm.forward_ragged(
            trainer.params, cfg, jnp.asarray(rb["dense"]),
            jnp.asarray(rb["indices"]), jnp.asarray(rb["offsets"]),
            max_l=max_l)))
        spread = max(float(np.abs(p - want).max()) for p in probs)
        print(f"round {rnd}: version {art.version} "
              f"({len(blob) / 1e3:.0f} kB artifact) adopted by "
              f"{args.replicas} replicas, loss {trainer.losses[-1]:.4f}, "
              f"max |replica - uncached| = {spread:.2e}")
        assert spread < 1e-4, "replica drifted from the live params"

    # out-of-order redelivery of an old artifact must be absorbed
    stale = VersionedHotCache(cache=replicas[0].cache, version=0)
    assert not stale.apply(replicas[0])
    hit = replicas[0].stats().get("cache_hit_rate") or 0.0
    print(f"stale artifact (v0) rejected; replica hit rate "
          f"{100.0 * hit:.1f}%")

    # every accepted swap snapshotted the outgoing version's hit counters
    # into its event — the per-version attribution the event log exists for
    attrib = replicas[0].telemetry.events.hit_rate_by_version()
    print("hit rate by served source version (replica 0, from the "
          "swap event log):")
    for v, hr in sorted(attrib.items()):
        print(f"  v{v}: "
              + ("no lookups" if hr is None else f"{100.0 * hr:.1f}%"))

    # full-source broadcast (VersionedSource): unlike the hot-only
    # artifact, this blob carries EVERY sparse-stage parameter (hot rows
    # + the whole cold arena), so a remote replica needs no by-reference
    # param sharing for the embedding stage — the arena-broadcast item.
    from repro.training import VersionedSource
    full_blob = trainer.publish_source()
    art = VersionedSource.deserialize(full_blob)
    fresh = RecEngine(cfg, dlrm.init(jax.random.PRNGKey(99), cfg),
                      source="cached", max_l=max_l, max_batch=8,
                      max_wait_ms=0.0, cache_k=k, cache_trace=trainer.hist)
    fresh.params = dict(fresh.params, **{
        kk: vv for kk, vv in trainer.params.items() if kk != "arena"})
    assert art.apply(fresh)
    rb = data.ragged_batch(4, mean_l=3, max_l=max_l)
    reqs = requests_from_ragged_batch(rb, cfg.n_tables)
    for r in reqs:
        fresh.submit(r)
    fresh.step(force=True)
    want = np.asarray(jax.nn.sigmoid(dlrm.forward_ragged(
        trainer.params, cfg, jnp.asarray(rb["dense"]),
        jnp.asarray(rb["indices"]), jnp.asarray(rb["offsets"]),
        max_l=max_l)))
    err = float(np.abs(np.asarray([r.prob for r in reqs]) - want).max())
    print(f"full-source artifact ({len(full_blob) / 1e3:.0f} kB, "
          f"v{art.version}) adopted by a cold replica: "
          f"max |prob - live| = {err:.2e}")
    assert err < 1e-4


def serve_fleet(args) -> None:
    """--fleet: the chaos-hardened fleet scenario. One group trainer, N
    replicas, TWO model variants (A = the trained dense head, B = a
    frozen candidate) A/B-served over one shared TableGroupSource; every
    broadcast carries source + head in one ``VersionedSource`` blob.
    With ``--chaos`` each replica's channel drops / duplicates / delays
    artifacts under a seeded, replayable schedule; recovery is asserted
    on BIT-exactness against a trainer-synced reference, not liveness."""
    from repro.fleet import CLEAN, FaultPlan, FleetRunner

    plan = (FaultPlan(seed=args.chaos_seed, drop=0.3, dup=0.3, delay=0.6,
                      max_delay=3) if args.chaos else CLEAN)
    n = max(2, args.replicas)
    rounds = max(2, args.online_steps // 4)     # refresh_every=4 inside
    fr = FleetRunner(n_replicas=n, plan=plan, seed=0)
    mode = (f"chaos (seed {plan.seed}: drop {plan.drop:.0%}, "
            f"dup {plan.dup:.0%}, delay {plan.delay:.0%} up to "
            f"{plan.max_delay} sends)" if args.chaos else "clean transport")
    print(f"fleet: 1 trainer -> {n} replicas x 2 variants (A/B) over one "
          f"shared table group; {mode}")
    for rnd in range(rounds):
        stats = fr.round()
        per_rep = " ".join(
            f"r{i}[+{s['applied']} ={s['republish']} !{s['stale']}]"
            for i, s in enumerate(stats["replicas"]))
        print(f"round {rnd}: v{stats['version']} {per_rep} "
              f"(in flight: "
              f"{[rep.channel.in_flight for rep in fr.replicas]})")

    inj = [rep.stale_injected for rep in fr.replicas]
    rej = [rep.stale_rejections() for rep in fr.replicas]
    print(f"stale accounting: injected {inj} == rejected {rej}")
    assert inj == rej, "channel/engine stale accounting disagrees"
    print(f"channel faults: dropped "
          f"{[rep.channel.dropped for rep in fr.replicas]}, duplicated "
          f"{[rep.channel.duplicated for rep in fr.replicas]}, delayed "
          f"{[rep.channel.delayed for rep in fr.replicas]}")
    print(f"pre-recovery exactness: {fr.exactness()}")

    rec = fr.recover(k=3)
    exact = all(all(flags) for flags in rec["exact"].values())
    print(f"recovery: {rec['bumps']} clean bump(s) -> exact={exact}, "
          f"recompiles={rec['recompiles']}")
    assert exact, "fleet did not recover to bit-exact serving"
    for per_model in rec["recompiles"]:
        assert all(x in (0, None) for x in per_model.values()), \
            "recovery path recompiled the serve step"

    print("hit rate by served version (replica 0, per model variant):")
    for model in ("a", "b"):
        attrib = fr.replicas[0].hit_rate_by_version(model)
        line = ", ".join(
            f"v{v}: " + ("-" if hr is None else f"{100.0 * hr:.0f}%")
            for v, hr in sorted(attrib.items()))
        print(f"  model {model}: {line}")


def serve_heterogeneous(args) -> None:
    """Heterogeneous table group: per-table composition (hot-cache the
    skewed tables, int8 the big ones), online per-table refresh under ONE
    version, per-table hit rates in stats()."""
    from repro.core import embedding_source as es
    from repro.training import OnlineGroupTrainer, VersionedSource

    from repro.configs.dlrm import DLRM_HET_SMOKE
    cfg = DLRM_HET_SMOKE
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    max_l = 2 * cfg.lookups_per_table
    # declare composition per table: cache the two skewed tables,
    # quantize the big one
    plans = dlrm.table_plans(cfg, cache_k=(64, 16, 0),
                             quantize_rows_above=1000)
    print("per-table plans:")
    for t, p in enumerate(plans):
        print(f"  table[{t}] vocab={p.rows} dim={p.dim} "
              f"cache_k={p.cache_k} int8={p.quantize}")

    trainer = OnlineGroupTrainer(cfg, params, max_l=max_l, plans=plans,
                                 refresh_every=args.cache_refresh)
    data = DLRMSynthetic(cfg, seed=23)
    pad = 16 * cfg.n_tables * max_l
    for _ in range(args.online_steps):
        trainer.train_step(data.ragged_batch(16, mean_l=3, max_l=max_l,
                                             pad_to=pad))
    if trainer.version == 0:
        # fewer steps than one refresh interval: force the first rebuild
        # so the published artifact is strictly newer than a fresh engine
        trainer.rebuild()
    print(f"trained {trainer.steps} steps, group version "
          f"{trainer.version}, loss {trainer.losses[-1]:.4f}")

    blob = trainer.publish_source()
    engine = RecEngine(cfg, trainer.params, source=trainer.serving_source(),
                       max_l=max_l, max_batch=8, max_wait_ms=0.0)
    engine.warmup()
    # a fresh engine serves at version 0; the broadcast artifact
    # (strictly newer) is adopted atomically
    assert VersionedSource.deserialize(blob).apply(engine)
    rb = data.ragged_batch(32, mean_l=3, max_l=max_l)
    reqs = requests_from_ragged_batch(rb, cfg.n_tables)
    for r in reqs:
        engine.submit(r)
    engine.step(force=True)
    engine.drain()
    s = engine.stats()
    print(f"served {s['n']} requests from the group "
          f"(v{s['cache_version']}, {len(blob) / 1e3:.0f} kB artifact); "
          f"p50 {s['p50_ms']:.2f} ms")
    print("per-table hit rates "
          "(None = that member serves no hot cache):")
    for t, hr in s["cache_hit_rate"].items():
        print(f"  table[{t}]: "
              + ("None" if hr is None else f"{100.0 * hr:.1f}%"))
    print(s["source_tree"])
    # exactness: group serving == the direct heterogeneous forward
    want = np.asarray(jax.nn.sigmoid(dlrm.forward_ragged(
        trainer.params, cfg, jnp.asarray(rb["dense"]),
        jnp.asarray(rb["indices"]), jnp.asarray(rb["offsets"]),
        max_l=max_l, source=engine.source)))
    got = np.asarray([r.prob for r in reqs])
    err = float(np.abs(got - want[:len(got)]).max())
    print(f"group serving vs direct forward: max err {err:.2e}")
    assert err < 1e-4


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=4096)
    parser.add_argument("--max-batch", type=int, default=64)
    parser.add_argument("--max-wait-ms", type=float, default=2.0)
    # 'sharded' is excluded: it requires a multi-device mesh this
    # single-host example does not build (see tests/test_sharded_sparse.py
    # and launch/train.py --shards for the sharded entry points)
    parser.add_argument("--path", choices=("fixed", "ragged", "cached"),
                        default="ragged")
    parser.add_argument("--dist", choices=("fixed", "uniform", "poisson"),
                        default="poisson")
    parser.add_argument("--cache-k", type=int, default=4096)
    parser.add_argument("--quantize-cold", action="store_true")
    parser.add_argument("--sla-ms", type=float, default=None,
                        help="latency SLA; default 10 ms closed-loop, "
                             "3x one measured batch time open-loop")
    parser.add_argument("--replicas", type=int, default=1,
                        help=">=2: run the trainer -> N-replica versioned "
                             "hot-arena broadcast demo instead")
    parser.add_argument("--online-steps", type=int, default=60)
    parser.add_argument("--cache-refresh", type=int, default=20)
    parser.add_argument("--het", action="store_true",
                        help="heterogeneous table-group demo: per-table "
                             "composition + online per-table refresh "
                             "under one version")
    parser.add_argument("--open-loop", action="store_true",
                        help="open-loop arrivals through the SLA-aware "
                             "continuous batcher (shed/downgrade under "
                             "overload) instead of the closed-loop wave")
    parser.add_argument("--qps", type=float, default=0.0,
                        help="offered arrival rate; 0 = calibrate from "
                             "measured capacity x --overload")
    parser.add_argument("--overload", type=float, default=2.0,
                        help="offered/capacity ratio when --qps is 0")
    parser.add_argument("--arrivals", choices=("poisson", "diurnal"),
                        default="poisson")
    parser.add_argument("--metrics-json", default=None,
                        help="write the telemetry registry snapshot "
                             "(+ swap events) to this path at exit")
    parser.add_argument("--trace", action="store_true",
                        help="collect per-request spans and enable "
                             "jax.profiler stage annotations")
    parser.add_argument("--live-fig5", action="store_true",
                        help="serve through per-stage device-timed jitted "
                             "stages and print the live Fig-5 "
                             "embedding-vs-MLP split")
    parser.add_argument("--fleet", action="store_true",
                        help="fleet scenario: 1 trainer -> N replicas x "
                             "2 A/B model variants over one shared table "
                             "group, full source+head broadcasts, "
                             "exactness-asserted recovery")
    parser.add_argument("--chaos", action="store_true",
                        help="with --fleet: drop/duplicate/delay/reorder "
                             "broadcasts on a seeded, replayable schedule")
    parser.add_argument("--chaos-seed", type=int, default=6,
                        help="fault-schedule seed for --chaos (6 = the "
                             "bench plan, guaranteed to drop AND reorder)")
    args = parser.parse_args()
    use_compile_cache()
    if args.fleet:
        serve_fleet(args)
    elif args.het:
        serve_heterogeneous(args)
    elif args.replicas > 1:
        serve_broadcast_fleet(args)
    elif args.open_loop:
        serve_open_loop(args)
    else:
        serve_once(args)


if __name__ == "__main__":
    main()
