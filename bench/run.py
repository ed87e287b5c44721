#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine this starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name through ``BENCHMARK.json`` (``bench/manifest.py``). The run refuses any
backend but a TPU with the Pallas kernels, makes its weights and traffic from
``--seed``, warms every shape the traffic uses, measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON line last on stdout. ``--trace 1`` records the window with the JAX
profiler and reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def device_info(jax, chips: int, peak_bytes: int) -> dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}


def peak_bytes(jax, chips: int) -> int:
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks: dict,
             t_start: float) -> dict:
    """Set up, measure, check. Returns the result line's fields."""
    import jax

    from bench import check, loadgen, xplane
    from bench.record import chip_mesh, clock

    kind = cell.traffic["kind"]
    mesh = chip_mesh(cell.chips)
    max_l = loadgen.max_len(cell.traffic["bag"])
    prof = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if kind == "open_loop":
            from bench.serve import Serve
            srv = Serve(cell.model, cell.config, cell.traffic, seed,
                        traced=trace, mesh=mesh)
            run, bodies, prob = srv.window(cell.traffic["rate_qps"],
                                           seconds, seed, prof)
            run.setup_s = srv.t0 - t_start
            run.backlog_at_close = srv.backlog_at_close
            run.peak_bytes = peak_bytes(jax, cell.chips)
            hlo = srv.hlo_texts(bodies) if trace else []
            srv.close()
            del srv
            gc.collect()
            checks, failed = check.serve(cell.model, cell.config, seed,
                                         bodies, prob, max_l)
            attempted = len(prob)
        elif kind == "closed_loop":
            from bench.train import CHECKED_STEPS, Train
            tr = Train(cell.model, cell.config, cell.traffic, seed, mesh)
            prog = tr.first_steps()
            t0 = clock()
            run = tr.window(seconds, prof)
            # the readings for the check are no part of set-up
            run.setup_s = t0 - t_start - tr.reading_s
            run.peak_bytes = peak_bytes(jax, cell.chips)
            hlo = tr.hlo_texts() if trace else []
            bodies = tr.checked_bodies()
            tr.close()
            del tr
            gc.collect()
            checks, failed = check.train(cell.model, cell.config, seed, prog,
                                         bodies, max_l)
            attempted = CHECKED_STEPS + len(run.steps)
        else:
            raise ValueError(f"unknown traffic kind {kind!r}")
        run.peaks = peaks
        if trace:
            run.trace = xplane.load_dir(prof, hlo, cell.chips)
    finally:
        if prof:
            shutil.rmtree(prof, ignore_errors=True)
    return {"run": run, "checks": checks, "failed": failed,
            "attempted": attempted}


def result_line(cell, out: dict, device: dict, trace: bool,
                root: Path) -> dict:
    from bench import manifest

    run = out["run"]
    metrics = {}
    for m in cell.metrics:
        v = manifest.reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": None, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        s = run.trace
        line["device"] = dict(device, busy_s=s.busy_s(0.0, run.window_s),
                              window_s=run.window_s)
        line["breakdown"] = {"device_ops": s.top_ops(10),
                             "idle_gaps": s.idle_gaps(10, run.window_s)}
    line["window_compiles"] = run.window_compiles
    if run.backlog_at_close is not None:
        line["backlog_at_close"] = run.backlog_at_close
    return line


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT,
         t_start: float = T_START) -> int:
    args = parse(argv)
    if require_chip:
        # the compile cache lives inside the checkout, at a fixed path
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    # this file's own directory holds modules named like the standard
    # library's; the package is imported from the checkout's root instead
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import manifest
    cell = manifest.cell(args.workload, bool(args.trace), root)

    import jax
    from repro import obs
    from repro.kernels import ops

    if require_chip:
        try:
            devs = jax.devices()
        except RuntimeError as e:
            print(f"bench: no JAX backend: {e}", file=sys.stderr)
            return 1
        if devs[0].platform != "tpu":
            print(f"bench: needs a TPU, JAX found {devs[0].platform}",
                  file=sys.stderr)
            return 1
        if len(devs) < cell.chips:
            print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
                  f"{len(devs)}", file=sys.stderr)
            return 1
        if ops.get_impl() != "pallas":
            print(f"bench: kernels run as {ops.get_impl()!r}, not pallas",
                  file=sys.stderr)
            return 1
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        peaks = manifest.peaks(devs[0].device_kind, root)
    else:
        peaks = manifest.peaks("TPU v5 lite", root)
    # metadata only, so traced and untraced runs share one compiled program
    obs.enable_stage_annotations(True)
    # the configuration states how float32 matmuls contract; the dots that
    # XLA emits itself (outside the program's kernels, such as those of a
    # custom gradient) follow JAX's default precision
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks,
                   t_start)
    from bench import check
    device = device_info(jax, cell.chips, out["run"].peak_bytes)
    line = result_line(cell, out, device, bool(args.trace), root)
    line["correct"] = check.passed(out["checks"])
    line["checks"] = {ch["name"]: {"value": ch["value"],
                                   "limit": ch["limit"]}
                      for ch in out["checks"]}
    for ch in out["checks"]:
        print(f"check {ch['name']} = {ch['value']!r} limit {ch['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
