"""Cells on a mesh of ``chips`` devices, on four virtual CPU devices: the
weights are drawn where each row lies, equal to those drawn whole, the
engine's arena and the trainer's are row-sharded over the four, and a
serving and a training cell come out correct. The test process keeps one
device, so the four live in a subprocess."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.helpers import ROOT, tiny_checkout

SEED = 2 ** 33 + 29

SCRIPT = r'''
import json, sys
from pathlib import Path

root, seed, cells = Path(sys.argv[1]), sys.argv[2], sys.argv[3:]
sys.path.insert(0, str(root))
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import run, serve, train, weights
from bench.record import chip_mesh
from bench.tests.helpers import TINY

out = {"devices": len(jax.devices())}
mesh = chip_mesh(4)
key = jax.device_put(weights.seed_key(9), NamedSharding(mesh, P()))
for c in (TINY, dict(TINY, n_tables=5, rows_per_table=37)):
    whole = weights.make(c, 9)
    parts = weights.make(c, 9, mesh)
    n = whole["arena"].shape[0]
    a = np.asarray(parts["arena"])
    # blocks of 4 rows, the last one drawn over its neighbour's
    small = weights._sharded_arena(key, weights._shape(c), mesh, block=64)
    out.setdefault("weights", []).append({
        "rows": [n, a.shape[0]],
        "equal": bool(np.array_equal(np.asarray(whole["arena"]), a[:n])
                      and np.array_equal(a, np.asarray(small))),
        "pad_zero": bool((a[n:] == 0).all()),
        "mlp_equal": all(
            np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
                jax.tree.leaves([whole["bottom"], whole["top"]]),
                jax.tree.leaves([parts["bottom"], parts["top"]]))),
        "shards": sorted(s.data.shape[0]
                         for s in parts["arena"].addressable_shards)})


def shards(arena):
    return [{"rows": s.data.shape[0], "device": s.device.id}
            for s in arena.addressable_shards]


serve_init, train_init = serve.Serve.__init__, train.Train.__init__


def watched_serve(self, *a, **kw):
    serve_init(self, *a, **kw)
    src = self.engine.source
    out["serve"] = {"source": type(src).__name__, "shards": src.n_shards,
                    "arena": shards(src.inner.arena)}


def watched_train(self, *a, **kw):
    train_init(self, *a, **kw)
    out["train"] = {"arena": shards(self.trainer.params["arena"])}


serve.Serve.__init__, train.Train.__init__ = watched_serve, watched_train
out["rc"] = [run.main(["--workload", cell, "--seed", seed, "--seconds", "1",
                       "--trace", "0"], require_chip=False, root=root)
             for cell in cells]
print(json.dumps(out))
'''


def four_chip_checkout(tmp):
    """The tiny checkout with its two cells also on four chips."""
    root = tiny_checkout(tmp)
    man = json.loads((root / "BENCHMARK.json").read_text())
    for w in list(man["workloads"]):
        if w["config"] == "tiny":
            man["workloads"].append(dict(w, name=w["name"] + "4", chips=4))
    for m in man["end_to_end"] + man["per_layer"]:
        for w in list(m.get("workloads", [])):
            if w.startswith("tiny."):
                m["workloads"].append(w + "4")
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=2))
    return root


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    root = four_chip_checkout(tmp_path_factory.mktemp("four"))
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(root), str(SEED),
                        "tiny.serve4", "tiny.train4"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    results = [json.loads(x) for x in lines if x.startswith('{"correct"')]
    return json.loads(lines[-1]), results


def test_weights_drawn_where_each_row_lies_equal_the_whole(four):
    out, _ = four
    assert out["devices"] == 4
    for w in out["weights"]:
        n, padded = w["rows"]
        assert padded % 4 == 0 and n <= padded < n + 4
        assert w["equal"] and w["pad_zero"] and w["mlp_equal"]
        assert w["shards"] == [padded // 4] * 4


def test_the_engine_and_the_trainer_row_shard_over_four_devices(four):
    out, _ = four
    assert out["serve"]["source"] == "ShardedArena"
    assert out["serve"]["shards"] == 4
    for arena in (out["serve"]["arena"], out["train"]["arena"]):
        assert sorted(s["device"] for s in arena) == [0, 1, 2, 3]
        assert {s["rows"] for s in arena} == {(3 * 500 + 1 + 3) // 4}


def test_cells_on_four_devices_are_correct(four):
    out, results = four
    assert out["rc"] == [0, 0] and len(results) == 2
    for line in results:
        assert line["correct"] is True and line["failed"] == 0, line
        assert line["device"]["count"] == 4
    assert results[0]["checks"]["ctr_max_abs_err"]["value"] < 1e-6
