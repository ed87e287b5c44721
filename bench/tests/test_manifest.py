"""BENCHMARK.json against the rules it is held to, and the promise that a
cell, a mix, a configuration or a metric is added by files alone."""
import json
import re
import sys

import pytest

from bench import manifest
from bench.tests.helpers import ROOT, tiny_checkout

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys_and_paths():
    assert set(MAN) == KEYS["top"]
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_entries_have_only_their_keys_and_legal_names(kind):
    want = KEYS[{"configs": "config", "workloads": "workload"}.get(kind,
                                                                  kind)]
    names = [e["name"] for e in MAN[kind]]
    assert len(names) == len(set(names))
    for e in MAN[kind]:
        extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
        assert want <= set(e) <= want | extra, e
        assert manifest.NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert manifest.UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
                assert "\t" not in e[key]


def test_every_cell_resolves_by_name():
    for w in MAN["workloads"]:
        assert w["chips"] in (1, 4)
        for trace in (False, True):
            cell = manifest.cell(w["name"], trace)
            assert cell.metrics, (w["name"], trace)
            for m in cell.metrics:
                assert callable(manifest.reader(m["name"]))
        e2e = {m["name"] for m in manifest.cell(w["name"]).metrics}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in MAN["per_layer"]:
        for w in m["workloads"]:
            e2e = {x["name"] for x in manifest.cell(w).metrics}
            assert m["moves"] in e2e, (m["name"], w)


def test_config_files_lie_under_paths_and_are_used():
    used = {w["config"] for w in MAN["workloads"]}
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]


def test_bounds_within_the_contract():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        manifest.peaks("TPU v99")
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A copy of the benchmark gains a configuration, two mixes and two
    cells by new files and entries, and the harness finds them by name."""
    root = tiny_checkout(tmp_path)
    sys.path.insert(0, str(root))
    try:
        for name in ("tiny.serve", "tiny.train"):
            cell = manifest.cell(name, False, root)
            assert cell.config["name"] == "tiny"
            assert cell.traffic["kind"] in ("open_loop", "closed_loop")
            assert {m["name"] for m in cell.metrics} >= {"setup_s"}
            for m in manifest.cell(name, True, root).metrics:
                assert callable(manifest.reader(m["name"], root))
    finally:
        sys.path.remove(str(root))


def test_file_names_use_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in (ROOT / "bench").rglob("*"):
        if "__pycache__" not in p.parts:
            assert ok.match(str(p.relative_to(ROOT))), p
