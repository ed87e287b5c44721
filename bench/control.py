#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, and the fault it
is held against, read at a cell's own size.

    python bench/control.py --workload <cell> --seeds 7,8,9 --seconds 20

The control is the plain reference put in the program's place, with its
matmuls one precision step below the configuration's: three bfloat16 passes
("high") where the configuration states float32 at "highest". For a serving
cell it answers every request that a run of the same seed and length
compares; for a training cell it takes the checked steps. A training cell is
also read with the fault of a step that leaves half its batch out. Prints one
JSON line per seed. The benchmark's own runs never run this: the limits in
the configuration files are set between these readings and those of the
program's runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def serve_readings(c: dict, mix: dict, seed: int, seconds: float) -> dict:
    """The serving checks with the control's CTRs in the program's
    place."""
    import numpy as np

    from bench import check, loadgen, reference, weights

    due = loadgen.poisson_arrivals(mix["rate_qps"], seconds,
                                   mix["shape_seed"], seed)
    n_due = int(np.searchsorted(due, seconds))
    bodies = loadgen.bodies(c, mix, len(due), seed)
    params = weights.make(c, seed)
    prob = reference.ctr(c, params, bodies, np.arange(n_due), "high",
                         max_l=mix["bag"]["max"])
    del params
    checks, _ = check.serve(c, seed, bodies, prob, mix["bag"]["max"])
    return {ch["name"]: ch["value"] for ch in checks}


def train_readings(c: dict, mix: dict, seed: int) -> dict:
    """The training numbers of the control and of the half-batch fault,
    each against the reference."""
    from bench import check, reference
    from bench.train import CHECKED_STEPS, batch_bodies

    batches = [reference.train_batch_of(b, mix["bag"]["max"])
               for b in batch_bodies(c, mix, seed, CHECKED_STEPS)]
    ref = reference.train(c, seed, batches)
    ctl = reference.train(c, seed, batches, precision="high")
    rows = reference.touched_rows(batches[0], c["n_tables"],
                                  c["rows_per_table"])
    half = reference.train(c, seed, [reference.half_batch(b, c["n_tables"])
                                     for b in batches], grad_rows=rows)
    return {"control": check.train_numbers(ctl, ref),
            "half_batch": check.train_numbers(half, ref)}


def readings(cell, seed: int, seconds: float) -> dict:
    if cell.traffic["kind"] == "open_loop":
        return serve_readings(cell.config, cell.traffic, seed, seconds)
    return train_readings(cell.config, cell.traffic, seed)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds")
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        q for q in sys.path if q != here]

    import jax

    from bench import manifest

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = manifest.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = {"workload": args.workload, "seed": seed,
               "readings": readings(cell, seed, args.seconds)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
