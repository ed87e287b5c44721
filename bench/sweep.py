#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest Poisson rate at which the
backlog does not grow over the window.

    python bench/sweep.py --workload dlrm5.serve.overload --lo 400 --hi 800 \\
        --steps 7 --seconds 30 [--out sweep.json]

One process builds the cell's serving stack once and offers each rate in
turn, from ``--lo`` up to ``--hi`` in geometric steps, each with a fresh
seed. A rate holds when every request due in the window settled in it but
for at most two full micro-batches, completed queries/s reach 97% of those
offered, and the backlog did not grow: the 90th percentile of queue wait
among requests due in the window's last third is at most twice that of its
first third, plus 5 ms. Prints one JSON line per rate and the knee last.
The rates written into the mix files are fixed fractions of this knee; the
benchmark itself never searches.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def knee_rates(lo: float, hi: float, steps: int) -> list:
    """Geometric steps from ``lo`` to ``hi``."""
    r = math.exp(math.log(hi / lo) / max(1, steps - 1))
    return [lo * r ** k for k in range(steps)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--lo", type=float, required=True)
    p.add_argument("--hi", type=float, required=True)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out")
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if p != here]

    import jax
    import numpy as np

    from bench import manifest, readers
    from bench.record import percentile
    from bench.serve import Serve
    from repro import obs
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    obs.enable_stage_annotations(True)
    cell = manifest.cell(args.workload)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    srv = Serve(cell.config, cell.traffic, args.seed, traced=False)
    rows, knee = [], None
    for k, rate in enumerate(knee_rates(args.lo, args.hi, args.steps)):
        run, _, prob = srv.window(rate, args.seconds, args.seed + 1 + k)
        late = int(np.sum(~(run.settle <= args.seconds)))
        row = {"rate_qps": rate, "offered_qps": len(run.due) / args.seconds,
               "completed_qps": readers.completed_qps(run),
               "p99_ms": readers.latency_p99_ms(run),
               "batch_mean": readers.batch_size_mean(run),
               "backlog_at_close": srv.backlog_at_close,
               "settled_after_close": late,
               "answered": int(np.isfinite(prob).sum()),
               "window_compiles": run.window_compiles}
        wait = run.dispatch - run.due
        third = args.seconds / 3
        first = percentile(wait[run.due < third], 90)
        last = percentile(wait[run.due >= 2 * third], 90)
        row["wait_p90_ms_first_third"] = 1e3 * first
        row["wait_p90_ms_last_third"] = 1e3 * last
        row["holds"] = bool(
            late <= 2 * cell.traffic["max_batch"]
            and row["completed_qps"] >= 0.97 * row["offered_qps"]
            and last <= 2 * first + 5e-3)
        if row["holds"]:
            knee = rate
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {"workload": args.workload, "seconds": args.seconds,
           "rows": rows, "knee_qps": knee}
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps({"knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
