"""A small copy of the benchmark that runs on the CPU: the same harness,
with a tiny configuration and mixes added as files."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY = {
    "name": "tiny", "source": "test", "n_tables": 3, "rows_per_table": 500,
    "emb_dim": 16, "lookups_per_table": 4, "dense_features": 13,
    "bottom_mlp": [32, 16], "top_mlp": [32, 1], "dtype": "float32",
    "matmul_precision": "highest",
    "optimizer": {
        "arena": {"rule": "rowwise_adagrad", "lr": 0.01, "eps": 1e-08},
        "mlp": {"rule": "adamw", "lr": 0.001, "b1": 0.9, "b2": 0.95,
                "eps": 1e-08, "weight_decay": 0.01}},
    "assumed": [],
    "limits": {"ctr_max_abs_err": 1e-05, "first_loss_rel_gap": 1e-05,
               "row_grad_gap": 1e-04, "change_norm_gap": 1e-03},
}
BAG = {"dist": "poisson", "mean": 4, "min": 1, "max": 8}
SERVE = {"kind": "open_loop", "arrivals": "poisson", "rate_qps": 200.0,
         "zipf_alpha": 1.05, "bag": BAG, "max_batch": 16,
         "buckets": [8, 16], "pipeline_depth": 2, "shape_seed": 11}
TRAIN = {"kind": "closed_loop", "batch": 16, "distinct_batches": 4,
         "zipf_alpha": 1.05, "bag": BAG, "shape_seed": 12}


def tiny_checkout(tmp: Path) -> Path:
    """A checkout with the real ``bench/`` plus a tiny configuration, two
    mixes and two cells, each added as files and entries only."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    (root / "bench/traffic/tiny_poisson.json").write_text(json.dumps(SERVE))
    (root / "bench/traffic/tiny_b16.json").write_text(json.dumps(TRAIN))
    man["configs"].append({"name": "tiny", "source": "test",
                           "file": "bench/configs/tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny_poisson",
         "chips": 1, "why": "test"},
        {"name": "tiny.train", "config": "tiny", "traffic": "tiny_b16",
         "chips": 1, "why": "test"}]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" not in m:
            continue
        if "dlrm5.serve.overload" in m["workloads"]:
            m["workloads"].append("tiny.serve")
        if "dlrm5.train" in m["workloads"]:
            m["workloads"].append("tiny.train")
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=2))
    return root
