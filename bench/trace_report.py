#!/usr/bin/env python3
"""Record one traced window of a cell with the program's own spans on, and
print where its time went.

    python bench/trace_report.py --workload <cell> --seed <n> --seconds <s> [--keep <prefix>]

The window is the one ``bench/run.py --trace 1`` records, with the serving
engine's or the trainer's ``Tracer`` switched on, so every program span
sits on the profiler's host plane beside the device ops. It prints one JSON
line: the end-to-end rate with tracing on, the cell's per-layer metrics,
device seconds by program scope, the ten longest idle gaps named by the
innermost span path covering them, host time by span, and backend compiles
by span. It checks nothing against the reference. ``--keep <prefix>``
writes the trace to ``<prefix>.xplane.pb.gz`` and the scope lines of the
compiled HLO to ``<prefix>.hlo.txt.gz``.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OP_NAME = re.compile(r'op_name="([^"]*)"')


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep", default=None)
    return p.parse_args(argv)


def traced_window(cell, seed: int, seconds: float, prof: str):
    """(run, tracer, HLO texts, end-to-end rate) of one window with the
    program's spans on."""
    from repro import obs

    from bench import readers
    from bench.record import chip_mesh
    mesh = chip_mesh(cell.chips)
    if cell.traffic["kind"] == "open_loop":
        from bench.serve import Serve
        srv = Serve(cell.model, cell.config, cell.traffic, seed, traced=True,
                    mesh=mesh)
        run, bodies, _ = srv.window(cell.traffic["rate_qps"], seconds,
                                    seed, prof)
        return (run, srv.engine.telemetry.tracer, srv.hlo_texts(bodies),
                {"serve_qps": readers.completed_qps(run)})
    from bench.train import Train
    tr = Train(cell.model, cell.config, cell.traffic, seed, mesh)
    tel = tr.trainer.telemetry
    tel.tracer = obs.Tracer(max_spans=1 << 20, registry=tel.registry)
    tr.first_steps()
    run = tr.window(seconds, prof)
    return (run, tel.tracer, tr.hlo_texts(),
            {"train_samples_s": readers.samples_per_s(run)})


def report(cell, run, tracer, spans, rate: dict, root: Path) -> dict:
    from bench import manifest
    from bench import program_trace as pt

    s = run.trace
    scopes = pt.scope_seconds(s)
    emb = scopes["emb_lookup"]
    split = pt.unwrapped(s).scope_s((pt.RELAYOUT, pt.GATHER_KERNEL), 0.0,
                                    float("inf"))
    compiles = {k: v for k, v in
                tracer.registry.snapshot()["counters"].items()
                if k.startswith("compiles_total")}
    return {
        "workload": cell.name, "window_s": run.window_s,
        "traced": {k: v for k, v in rate.items() if v is not None},
        "metrics": {m["name"]: manifest.reader(m["name"], root)(run)
                    for m in cell.metrics},
        "scopes_s": scopes,
        "outside_share": (scopes["outside"] / scopes["busy"]
                          if scopes["busy"] else None),
        "emb_lookup_split_share": split / emb if emb else None,
        "idle_by_span": pt.idle_by_span(s, spans, 10, run.window_s),
        "idle_gaps": s.idle_gaps(10, run.window_s),
        "program_spans": pt.span_readings(spans, run.window_s),
        "dropped": tracer.dropped,
        "window_compiles": run.window_compiles,
        "compiles_total": compiles,
    }


def scope_lines(hlo: list) -> str:
    """The instruction lines of the compiled HLO, cut to what
    ``xplane.scope_map`` reads: name, result type and ``op_name``."""
    from bench import xplane

    out = []
    for text in hlo:
        for line in text.splitlines():
            key = xplane.op_key(line)
            if key:
                m = OP_NAME.search(line)
                out.append(f"{key[0]} = {key[1]}" + (
                    f' metadata={{op_name="{m.group(1)}"}}' if m else ""))
    return "\n".join(out)


def keep(prefix: str, pb: str, hlo: list) -> None:
    """The trace and the scope lines of the HLO, gzipped, for a test."""
    Path(prefix).parent.mkdir(parents=True, exist_ok=True)
    with open(pb, "rb") as f:
        Path(f"{prefix}.xplane.pb.gz").write_bytes(gzip.compress(f.read()))
    Path(f"{prefix}.hlo.txt.gz").write_bytes(
        gzip.compress(scope_lines(hlo).encode()))


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT) -> int:
    args = parse(argv)
    if require_chip:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import manifest, program_trace, xplane
    cell = manifest.cell(args.workload, True, root)

    import jax
    from repro import obs
    from repro.kernels import ops

    if require_chip:
        devs = jax.devices()
        if devs[0].platform != "tpu" or ops.get_impl() != "pallas":
            print(f"trace_report: needs a TPU with the Pallas kernels, "
                  f"found {devs[0].platform}/{ops.get_impl()}",
                  file=sys.stderr)
            return 1
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        peaks = manifest.peaks(devs[0].device_kind, root)
    else:
        peaks = manifest.peaks("TPU v5 lite", root)
    obs.enable_stage_annotations(True)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])

    prof = tempfile.mkdtemp(prefix="trace_report_")
    try:
        run, tracer, hlo, rate = traced_window(cell, args.seed,
                                               args.seconds, prof)
        run.peaks = peaks
        (pb,) = glob.glob(f"{prof}/**/*.xplane.pb", recursive=True)
        run.trace = xplane.load(pb, hlo, cell.chips)
        spans = program_trace.host_spans(pb)
        if args.keep:
            keep(args.keep, pb, hlo)
    finally:
        shutil.rmtree(prof, ignore_errors=True)
    line = report(cell, run, tracer, spans, rate, root)
    line["device"] = {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
