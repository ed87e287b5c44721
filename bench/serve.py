"""Open-loop serving: Poisson arrivals into ``SlaScheduler`` over a
``RecEngine(source="ragged")``.

One thread offers the load and runs the serving loop, as the program's
scheduler is driven: each due request is submitted, then ``pump`` runs one
scheduling turn; with nothing queued or in flight the loop waits for the next
arrival. Latency runs from each request's *due* time to its settle, so a late
generator or a stalled loop shows in it. Shedding and the int8 downgrade are
off: shedding would hide load, and the downgrade serves a different result.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

from bench import loadgen
from bench.record import (Run, annotate, clock, compile_counter,
                          start_trace)


class Serve:
    """The serving stack of one cell, warmed for every bucket its traffic
    uses; ``window`` offers one open-loop load to it. ``model`` is the
    configuration's model module; on a ``mesh`` the engine's arena is
    row-sharded over it."""

    def __init__(self, model, c: dict, mix: dict, seed: int, *,
                 traced: bool, mesh=None):
        from repro import obs
        from repro.serving import RecEngine
        from repro.serving.scheduler import SlaPolicy, SlaScheduler

        self.model, self.c, self.mix = model, c, mix
        params = model.weights(c, seed, mesh)
        jax.block_until_ready(params)
        self.engine = RecEngine(
            model.program_config(c), params, source="ragged",
            max_l=loadgen.max_len(mix["bag"]), max_batch=mix["max_batch"],
            buckets=tuple(mix["buckets"]), mesh=mesh,
            telemetry=obs.Telemetry(tracing=traced, max_spans=1 << 20))
        self.sched = SlaScheduler(
            self.engine, SlaPolicy(allow_shed=False, allow_downgrade=False),
            pipeline_depth=mix["pipeline_depth"])
        # compiles and runs every bucket; the estimator's timed probes are
        # left out, since nothing is shed or downgraded
        self.sched.warmup(calibrate=False)
        self._wrap_engine()

    def _wrap_engine(self):
        eng = self.engine
        dispatch, settle = eng.dispatch, eng.settle

        def timed_dispatch(reqs, **kw):
            with annotate("dispatch"):
                ib = dispatch(reqs, **kw)
            t = clock() - self.t0
            ids = 0
            for r in reqs:
                self.t_dispatch[r.rid] = t
                ids += int(self.n_ids[r.rid])
            self.batches.append((t, len(reqs), ids))
            self.inflight.append(ib)
            return ib

        def timed_settle(ib):
            with annotate("settle"):
                n = settle(ib)
            t = clock() - self.t0
            for r in ib.reqs:
                self.t_settle[r.rid] = t
                self.prob[r.rid] = r.prob
            self.inflight.remove(ib)
            return n

        eng.dispatch, eng.settle = timed_dispatch, timed_settle

    def requests(self, b: loadgen.Bodies):
        from repro.serving import RecRequest
        t = b.lens.shape[1]
        cuts = b.offsets[1:-1]
        per_bag = np.split(b.ids, cuts)
        return [RecRequest(rid=i, dense=b.dense[i],
                           sparse_ids=per_bag[i * t:(i + 1) * t])
                for i in range(b.n)]

    def window(self, rate_qps: float, seconds: float, seed: int,
               profile_dir: str = None) -> tuple:
        """Offer Poisson load at ``rate_qps`` for ``seconds``; drain what
        is left afterwards (checked, its latency counting the wait).
        Returns the run record, the bodies and the served CTRs."""
        # the collector would walk the growing heap of request objects
        # over and over while they are made (seconds at tens of thousands)
        gc.disable()
        due = loadgen.poisson_arrivals(rate_qps, seconds,
                                       self.mix["shape_seed"], seed)
        b = self.model.bodies(self.c, self.mix, len(due), seed)
        reqs = self.requests(b)
        gc.enable()
        n = len(reqs)
        self.n_ids = b.ids_per_request()
        self.t_dispatch = np.full(n, np.nan)
        self.t_settle = np.full(n, np.nan)
        self.prob = np.full(n, np.nan)
        self.batches = []
        self.inflight = []
        submit = np.full(n, np.nan)
        sched = self.sched
        # the pre-made requests belong to the load generator: kept out of
        # the collector's passes, which would otherwise walk every one of
        # them inside the window (a third of a second at tens of thousands)
        gc.collect()
        gc.freeze()
        if profile_dir:
            start_trace(profile_dir)
        i = 0
        with compile_counter() as compiles, annotate("window"):
            self.t0 = t0 = clock()
            while True:
                now = clock() - t0
                if now >= seconds:
                    break
                while i < n and due[i] <= now:
                    reqs[i].submitted_mono = t0 + due[i]
                    submit[i] = now
                    sched.submit(reqs[i])
                    i += 1
                if len(sched) or sched.inflight:
                    with annotate("pump"):
                        sched.pump()
                else:
                    until = t0 + min(due[i] if i < n else seconds, seconds)
                    with annotate("gen_wait"):
                        while clock() < until:
                            pass
        self.backlog_at_close = self.backlog()
        if profile_dir:
            # the trace holds the whole device work of every batch the
            # window dispatched, and nothing of the drain
            jax.block_until_ready([ib.probs for ib in self.inflight])
            jax.profiler.stop_trace()
        n_due = int(np.searchsorted(due, seconds))
        while i < n_due:
            reqs[i].submitted_mono = t0 + due[i]
            submit[i] = clock() - t0
            sched.submit(reqs[i])
            i += 1
        sched.drain()
        gc.unfreeze()
        run = Run(config=self.c, traffic=self.mix, peaks={},
                  window_s=seconds, window_compiles=compiles[0],
                  due=due[:n_due], submit=submit[:n_due],
                  dispatch=self.t_dispatch[:n_due],
                  settle=self.t_settle[:n_due], batches=self.batches)
        for t, k, ids in self.batches:
            if t <= seconds:
                run.add_work(self.model.serve_work(self.c, ids, k))
        spans = self.engine.telemetry.tracer.finished
        for s in spans:
            if s.name in ("dispatch", "settle"):
                run.host_spans.setdefault(s.name, []).append(s.duration_ms)
        return run, b, self.prob[:n_due].copy()

    def hlo_texts(self, bodies) -> list:
        """The compiled serve step of every bucket, as HLO text."""
        eng = self.engine
        reqs = self.requests(bodies)
        out = []
        for bucket in eng.buckets:
            batch, _ = eng._assemble(reqs[:bucket], bucket)
            out.append(eng._serve.lower(eng.params, batch, eng.source)
                       .compile().as_text())
        return out

    def backlog(self) -> int:
        return len(self.sched) + self.sched.inflight

    def close(self):
        self.sched = None
        self.engine = None
