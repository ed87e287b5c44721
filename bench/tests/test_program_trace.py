"""The readers of the program's own scopes and spans, on made-up intervals,
on two recorded chip traces, and through ``bench/trace_report.py`` on the
CPU.

``bench/testdata/serve_dlrm5.*`` is ``trace_report.py --keep`` of a 0.6 s
window of ``dlrm5.serve.overload`` (seed 3130000003) on a TPU v5 lite: 13
batches dispatched, 11 settled inside the window, with the program's spans
and the scopes below the stages. ``serve_dlrm6.*`` (see ``test_trace.py``)
predates both."""
import gzip
import json

import numpy as np
import pytest

from bench import program_trace as pt
from bench import readers, trace_report, xplane
from bench.record import Run
from bench.tests.helpers import BENCH, tiny_checkout

DATA = BENCH / "testdata"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def older(tmp_path_factory):
    """The dlrm6 serve trace, recorded before the scopes below the stages
    existed: (summary, program spans)."""
    pb = tmp_path_factory.mktemp("older") / "serve.xplane.pb"
    pb.write_bytes(gzip.decompress(
        (DATA / "serve_dlrm6.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress(
        (DATA / "serve_dlrm6.hlo.txt.gz").read_bytes()).decode()
    return xplane.load(str(pb), [hlo]), pt.host_spans(str(pb))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The dlrm5 serve window: (summary, program spans)."""
    pb = tmp_path_factory.mktemp("recorded") / "serve.xplane.pb"
    pb.write_bytes(gzip.decompress(
        (DATA / "serve_dlrm5.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress(
        (DATA / "serve_dlrm5.hlo.txt.gz").read_bytes()).decode()
    return xplane.load(str(pb), [hlo]), pt.host_spans(str(pb))


WINDOW = 0.6


def made_up():
    # emb_lookup [0, 4): relayout [0, 2.5), gather_reduce [2.5, 4); the
    # sparse update [5, 6); the head's backward [6, 6.5); no scope [7, 7.5)
    return xplane.Summary(
        starts=np.array([0.0, 0.5, 2.5, 5.0, 6.0, 7.0]),
        ends=np.array([2.5, 2.0, 4.0, 6.0, 6.5, 7.5]),
        names=["%while", "%fusion", "%fused_segment_sum", "%scatter",
               "%dot", "%copy"],
        scopes=["f/emb_lookup/relayout/while",
                "f/emb_lookup/relayout/while/body/x",
                "f/emb_lookup/gather_reduce/pallas_call",
                "f/sparse_update/scatter",
                "f/transpose(jvp(mlp))/dot_general", ""])


def test_new_device_readers_on_made_up_intervals():
    run = Run(config={}, traffic={}, peaks=PEAKS, window_s=10.0,
              batches=[(0.0, 128, 1000), (5.0, 64, 500), (11.0, 8, 10)],
              steps=[(0.0, 5.0, 256), (5.0, 10.0, 256)], trace=made_up())
    run.add_work({"gather": (0, 819e9 * 0.015)})      # 15 ms of bytes
    assert pt.relayout_ms_per_batch(run) == pytest.approx(2500.0 / 2)
    assert pt.gather_kernel_roofline_pct(run) == pytest.approx(1.0)
    # the whole lookup scope reads the relayout too
    assert readers.gather_roofline_pct(run) == pytest.approx(
        100 * 0.015 / 4.0)
    assert pt.sparse_update_ms_per_step(run) == pytest.approx(500.0)
    # the existing reader still counts the update outside every stage
    assert readers.outside_stages_ms_per_step(run) == pytest.approx(
        1e3 * 2.0 / 2)


def test_new_device_readers_are_none_without_their_scopes(older):
    s, _ = older
    run = Run(config={}, traffic={}, peaks=PEAKS, window_s=0.01,
              batches=[(0.0, 128, 1000)], steps=[(0.0, 0.01, 8)], trace=s)
    run.add_work({"gather": (0, 1e6)})
    assert pt.relayout_ms_per_batch(run) is None
    assert pt.gather_kernel_roofline_pct(run) is None
    assert pt.sparse_update_ms_per_step(run) is None
    run.trace = None
    assert pt.relayout_ms_per_batch(run) is None
    assert pt.gather_kernel_roofline_pct(run) is None
    assert pt.sparse_update_ms_per_step(run) is None


def test_existing_readers_read_the_same_on_the_older_trace(older):
    s, _ = older
    hi = float(s.ends.max())
    assert hi == pytest.approx(0.008051069, abs=1e-12)
    assert s.busy_s(0.0, hi) == pytest.approx(0.000844242, abs=1e-12)
    assert s.scope_s(readers.GATHER, 0.0, np.inf) == pytest.approx(
        0.000745255, abs=1e-12)
    assert s.scope_s(readers.HEAD, 0.0, np.inf) == pytest.approx(
        3.118e-05, abs=1e-12)
    assert s.outside_s(readers.STAGES, 0.0, np.inf) == pytest.approx(
        6.7807e-05, abs=1e-12)
    run = Run(config={}, traffic={}, peaks=PEAKS, window_s=hi, trace=s)
    run.add_work({"gather": (0, 1e6)})
    run.add_work({"head": (1e9, 1e5)})
    assert readers.gather_roofline_pct(run) == pytest.approx(
        100 * 1e6 / 819e9 / 0.000745255, rel=1e-9)
    assert readers.dense_roofline_pct(run) == pytest.approx(
        100 * 1e9 / 197e12 / 3.118e-05, rel=1e-9)
    assert readers.device_idle_pct(run) == pytest.approx(
        100 * (1 - 0.000844242 / hi), rel=1e-9)


def test_autodiff_wrappers_unwrap_to_their_stage():
    s = pt.unwrapped(made_up())
    assert s.scopes[4] == "f/mlp/dot_general"
    assert s.scopes[0] == "f/emb_lookup/relayout/while"
    by = pt.scope_seconds(made_up())
    assert by["mlp"] == pytest.approx(0.5)
    assert by["relayout"] + by["gather_reduce"] == pytest.approx(
        by["emb_lookup"])
    assert by["busy"] == pytest.approx(6.0)
    assert by["outside"] == pytest.approx(0.5)       # only the copy


def serve_turn():
    """One traced turn: the harness's pump around the program's."""
    events = [(0.0, 10.0, "pump"), (0.1, 9.9, "pump"),
              (0.2, 3.0, "dispatch"), (0.3, 1.0, "bucket_pad"),
              (3.05, 3.1, "plan"),
              (4.0, 9.5, "settle"), (4.1, 9.0, "settle_wait"),
              (9.0, 9.4, "respond"), (10.5, 11.0, "gen_wait")]
    return pt.nest([(a, b, n, 0) for a, b, n in events])


def test_spans_nest_by_line_and_a_same_name_chain_counts_once():
    spans = serve_turn()
    paths = {(sp.path, sp.shadowed) for sp in spans}
    assert ("pump", True) in paths and ("pump", False) in paths
    assert {p for p, _ in paths} == {
        "pump", "pump/dispatch", "pump/dispatch/bucket_pad", "pump/plan",
        "pump/settle", "pump/settle/settle_wait", "pump/settle/respond",
        "gen_wait"}
    # another thread's events never nest under this one's
    other = pt.nest([(0.0, 5.0, "pump", 0), (1.0, 2.0, "plan", 1)])
    assert [sp.path for sp in other] == ["pump", "plan"]


def test_idle_gaps_are_named_by_the_innermost_span_covering_them():
    s = xplane.Summary(starts=np.array([0.0, 3.2, 9.45]),
                       ends=np.array([0.25, 4.05, 10.2]),
                       names=["%a", "%b", "%c"], scopes=["", "", ""])
    assert s.gaps(12.0) == [(0.25, 3.2), (4.05, 9.45), (10.2, 12.0)]
    got = pt.idle_by_span(s, serve_turn(), 5, 12.0)
    assert [p for p, _ in got] == ["pump/settle/settle_wait",
                                   "pump/dispatch", ""]
    assert [g for _, g in got] == pytest.approx([5.4, 2.95, 1.8])
    assert len(pt.idle_by_span(s, serve_turn(), 1, 12.0)) == 1


def test_span_readings_per_batch_turn_and_step():
    got = pt.span_readings(serve_turn(), 12.0)
    assert got["settle_wait_ms_per_batch"] == pytest.approx(4900.0)
    # the program's pump less its wait, per batch
    assert got["host_self_ms_per_batch"] == pytest.approx(9800.0 - 4900.0)
    assert got["plan_ms_per_turn"] == pytest.approx(50.0)
    assert got["spans"]["pump"] == [1, pytest.approx(9.8)]
    assert "train_host_self_ms_per_step" not in got
    steps = pt.nest([(0.0, 1.0, "train_step", 0), (0.1, 0.2, "feed", 0),
                     (0.2, 0.3, "step", 0), (0.3, 0.35, "rows", 0),
                     (0.35, 0.95, "loss_sync", 0),
                     (1.0, 2.0, "train_step", 0),
                     (1.1, 1.9, "loss_sync", 0)])
    got = pt.span_readings(steps, 2.0)
    assert got["train_host_self_ms_per_step"] == pytest.approx(
        1e3 * (2.0 - 0.6 - 0.8) / 2)
    assert "settle_wait_ms_per_batch" not in got
    assert pt.span_readings([], 1.0) == {"spans": {}}


def test_recorded_scopes_split_the_lookup(recorded):
    s, _ = recorded
    by = pt.scope_seconds(s)
    split = s.scope_s((pt.RELAYOUT, pt.GATHER_KERNEL), 0.0, np.inf)
    assert split >= 0.95 * by["emb_lookup"]
    assert 0.2 < by["relayout"] / by["emb_lookup"] < 0.6
    assert by["outside"] < 0.01 * by["busy"]
    ops = dict(zip(s.names, s.scopes))
    assert "/emb_lookup/relayout/" in ops["%while.4"]      # searchsorted
    kernels = [p for n, p in ops.items() if n.startswith("%fused_segment")]
    assert kernels and all("/emb_lookup/gather_reduce/" in p
                           for p in kernels)


def test_recorded_new_readers(recorded):
    s, spans = recorded
    batches = [(sp.start, 64, 5000) for sp in spans
               if sp.name == "dispatch" and not sp.shadowed
               and sp.end <= WINDOW]
    assert len(batches) == 13
    run = Run(config={}, traffic={}, peaks=PEAKS, window_s=WINDOW,
              batches=batches, trace=s)
    run.add_work({"gather": (0, 1e9)})
    relayout = s.scope_s((pt.RELAYOUT,), 0.0, np.inf)
    assert pt.relayout_ms_per_batch(run) == pytest.approx(
        1e3 * relayout / 13)
    emb = s.scope_s(("emb_lookup",), 0.0, np.inf)
    kernel = s.scope_s((pt.GATHER_KERNEL,), 0.0, np.inf)
    assert pt.gather_kernel_roofline_pct(run) == pytest.approx(
        readers.gather_roofline_pct(run) * emb / kernel)
    assert pt.sparse_update_ms_per_step(run) is None      # no steps


def test_recorded_spans_nest_and_name_every_idle_gap(recorded):
    s, spans = recorded
    assert {sp.path for sp in spans} == {
        "pump", "pump/plan", "pump/dispatch", "pump/dispatch/bucket_pad",
        "pump/settle", "pump/settle/settle_wait", "pump/settle/respond"}
    # the harness's pump, dispatch and settle each hold the program's own
    for name in ("pump", "dispatch", "settle"):
        outer = [sp for sp in spans if sp.name == name and sp.shadowed]
        inner = [sp for sp in spans if sp.name == name and not sp.shadowed]
        assert len(outer) == len(inner) > 0
    gaps = pt.idle_by_span(s, spans, 1000, WINDOW)
    assert sum(g for _, g in gaps) == pytest.approx(
        WINDOW - s.busy_s(0.0, WINDOW))
    assert gaps[0][0] == "pump/dispatch"
    assert all(p for p, g in gaps if g >= 1e-3)
    # the harness's labels name the same gaps by their outer annotation
    assert [g for _, g in s.idle_gaps(10, WINDOW)] == pytest.approx(
        [g for _, g in gaps[:10]])
    got = pt.span_readings(spans, WINDOW)
    assert got["spans"]["pump/dispatch"][0] == 13
    assert got["spans"]["pump/settle"][0] == 11
    assert got["settle_wait_ms_per_batch"] > 10 * got["spans"][
        "pump/settle/respond"][1] / 11 * 1e3


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.train"])
def test_trace_report_runs_a_cell_with_the_program_spans_on(
        checkout, cell, tmp_path, capsys, monkeypatch):
    """On the CPU there is no device plane: the device side is a made-up
    summary, the host side the real trace of the window."""
    monkeypatch.setattr(xplane, "load", lambda path, hlo, chips: made_up())
    rc = trace_report.main(["--workload", cell, "--seed", str(2 ** 33 + 5),
                            "--seconds", "0.5", "--keep",
                            str(tmp_path / "kept")],
                           require_chip=False, root=checkout)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["dropped"] == 0 and line["window_compiles"] == 0
    assert line["emb_lookup_split_share"] == pytest.approx(1.0)
    assert line["outside_share"] == pytest.approx(0.5 / 6.0)
    spans = line["program_spans"]["spans"]
    if cell == "tiny.serve":
        assert line["traced"]["serve_qps"] > 0
        assert {"pump/plan", "pump/dispatch/bucket_pad",
                "pump/settle/settle_wait", "pump/settle/respond"} <= set(spans)
        assert line["program_spans"]["settle_wait_ms_per_batch"] > 0
        assert line["metrics"]["relayout_ms_per_batch.overload"] > 0
    else:
        assert line["traced"]["train_samples_s"] > 0
        assert {"train_step/feed", "train_step/step", "train_step/rows",
                "train_step/loss_sync"} <= set(spans)
        assert line["program_spans"]["train_host_self_ms_per_step"] > 0
        assert line["metrics"]["sparse_update_scope_ms.train"] > 0
        # the first step compiled inside its ``step`` span
        assert line["compiles_total"] == {'compiles_total{span="step"}': 1.0}
    assert (tmp_path / "kept.xplane.pb.gz").stat().st_size > 0
    assert (tmp_path / "kept.hlo.txt.gz").stat().st_size > 0
