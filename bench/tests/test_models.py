"""A configuration's model module: ``dlrm_dot`` gives, seed for seed, what
the harness gave before it took the model from a module, and a model is
added by new files alone."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import loadgen, manifest, run
from bench.tests.helpers import BENCH, ROOT, SERVE, TINY, TRAIN, \
    tiny_checkout
from bench.train import batch_bodies

SEED = 2 ** 33 + 17
DLRM5 = json.loads((ROOT / "bench/configs/dlrm5.json").read_text())


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:32]


def _mix(name):
    return json.loads((ROOT / f"bench/traffic/{name}.json").read_text())


def _pinned(what: str):
    m = manifest.model(TINY)
    serve = m.bodies(TINY, SERVE, 64, SEED)
    train = batch_bodies(m, TINY, TRAIN, SEED, TRAIN["distinct_batches"])
    max_l = loadgen.max_len(SERVE["bag"])
    if what == "weights":
        return digest(jax.tree.leaves(m.weights(TINY, SEED)))
    if what == "bodies":
        return digest([serve.dense, serve.lens, serve.ids, serve.offsets,
                       serve.labels])
    if what == "train_batches":
        return digest([v for b in train for _, v in
                       sorted(m.train_batch(TINY, TRAIN, b).items())])
    if what == "reference_ctr":
        return digest([m.reference_ctr(TINY, SEED, serve, np.arange(24),
                                       max_l)])
    if what == "reference_train":
        ref = m.reference_train(TINY, SEED, train[:3], max_l)
        return digest([np.asarray(ref[k], np.float64) for k in (
            "losses", "grad_norms", "row_grad_norms", "change_norms")])
    if what == "serve_work":
        return m.serve_work(TINY, int(serve.ids.size), serve.n)
    if what == "train_work":
        return m.train_work(TINY, train[0], TRAIN["batch"])
    m5 = manifest.model(DLRM5)
    if what == "dlrm5_serve_bodies":
        b = m5.bodies(DLRM5, _mix("overload_dlrm5"), 16, SEED)
        return digest([b.dense, b.lens, b.ids, b.offsets, b.labels])
    mix = _mix("closed_b256")
    (b,) = batch_bodies(m5, DLRM5, mix, SEED, 1)
    return digest([v for _, v in sorted(m5.train_batch(DLRM5, mix,
                                                       b).items())])


# recorded from the harness before the model moved behind its module
PARENT = {
    "weights": "e83a51eb1239e9b7206f83055e66786f",
    "bodies": "a0c1d03072d9bc751fa063069b87ef46",
    "train_batches": "5299d728a3751c55416d7f3a0faa1595",
    "reference_ctr": "473ba942807710f9b8a4f09507952e0f",
    "reference_train": "4e16fccd46ef75e126c177a85c5c0d7b",
    "serve_work": {"gather": (12224, 64240), "head": (225280, 22852)},
    "train_work": {"gather": (3056, 16060), "head": (168960, 7876),
                   "update": (13616, 68156)},
    "dlrm5_serve_bodies": "df50211abe5a63d4e9accde155a7f845",
    "dlrm5_train_batch": "3205ebd6ec1db1f150ae6dfaf1dfd346",
}


@pytest.mark.parametrize("what", sorted(PARENT))
def test_dlrm_dot_gives_what_the_harness_gave(what):
    assert _pinned(what) == PARENT[what]


def test_a_configuration_without_a_model_runs_dlrm_dot():
    assert "model" not in DLRM5
    m = manifest.model(DLRM5)
    assert m.__file__ == str(BENCH / "models/dlrm_dot.py")
    with pytest.raises(ValueError):
        manifest.model(dict(DLRM5, model="../run"))


# -- per-table rows and fixed bags ------------------------------------------

HET = dict(TINY, table_rows=[500, 37, 4])
FIXED = {"dist": "fixed", "lengths": [1, 3, 12]}


def test_table_ids_fall_in_their_own_rows():
    mix = dict(SERVE, bag=FIXED)
    b = loadgen.bodies(HET, mix, 200, SEED)
    table = np.repeat(np.tile(np.arange(3), b.n), b.lens.reshape(-1))
    for t, rows in enumerate(HET["table_rows"]):
        ids = b.ids[table == t]
        assert ids.min() >= 0 and ids.max() < rows
        # Zipf ranks reach the top of a small table
        assert ids.max() == rows - 1 or rows > 100
    assert b.ids[table == 0].max() >= 37


def test_fixed_bags_give_each_table_its_length():
    mix = dict(TRAIN, bag=FIXED)
    b = loadgen.bodies(TINY, mix, TRAIN["batch"], SEED)
    assert (b.lens == np.array(FIXED["lengths"])).all()
    assert loadgen.max_len(FIXED) == 12
    batch = loadgen.train_batch(TINY, mix, b)
    # padded to the real need, which every batch fills
    assert batch["indices"].size == TRAIN["batch"] * 16 == b.ids.size
    with pytest.raises(ValueError):
        loadgen.bodies(TINY, dict(mix, bag={"dist": "fixed",
                                            "lengths": [1, 2]}), 4, SEED)


def test_poisson_padding_is_unchanged():
    b = loadgen.bodies(TINY, TRAIN, TRAIN["batch"], SEED)
    batch = loadgen.train_batch(TINY, TRAIN, b)
    assert batch["indices"].size == TRAIN["batch"] * 3 * TRAIN["bag"]["max"]
    assert loadgen.max_len(TRAIN["bag"]) == TRAIN["bag"]["max"]


# -- a model added by new files alone ---------------------------------------

# the dot DLRM with a reference of its own: the served CTR in float64 numpy
MODEL = '''
"""A test model: dlrm_dot, with a float64 numpy reference of the CTR."""
import sys

import jax
import numpy as np

from bench import weights as _weights
from bench.models import dlrm_dot

program_config = dlrm_dot.program_config
weights = dlrm_dot.weights
bodies = dlrm_dot.bodies
train_batch = dlrm_dot.train_batch
reference_train = dlrm_dot.reference_train
first_step_readings = dlrm_dot.first_step_readings
change_readings = dlrm_dot.change_readings
serve_work = dlrm_dot.serve_work
train_work = dlrm_dot.train_work


def _mlp(layers, x):
    for i, (w, b) in enumerate(layers):
        x = x @ w + b
        if i < len(layers) - 1:
            x = np.maximum(x, 0.0)
    return x


def reference_ctr(c, seed, bodies, sel, max_l, precision="highest"):
    if precision != "highest":
        raise ValueError(precision)
    print(f"dot_np: reference of {len(sel)} requests", file=sys.stderr)
    p = jax.tree.map(lambda x: np.asarray(x, np.float64),
                     _weights.make(c, seed))
    t, rows = c["n_tables"], c["rows_per_table"]
    out = []
    for i in sel:
        bags = [p["arena"][j * rows + bodies.ids[
            bodies.offsets[i * t + j]:bodies.offsets[i * t + j + 1]]].sum(0)
            for j in range(t)]
        bot = _mlp(p["bottom"], bodies.dense[i].astype(np.float64))
        f = np.stack([bot] + bags)
        z = f @ f.T
        li, lj = np.tril_indices(t + 1, -1)
        x = np.concatenate([bot, z[li, lj]])
        out.append(1.0 / (1.0 + np.exp(-_mlp(p["top"], x)[0])))
    return np.asarray(out)
'''


def _add_model(root):
    """A model module, a configuration naming it, mixes of fixed per-table
    bags and two cells: new files and entries only."""
    bench = root / "bench"
    (bench / "models/dot_np.py").write_text(MODEL)
    c = dict(HET, name="tinyhet", model="dot_np")
    (bench / "configs/tinyhet.json").write_text(json.dumps(c))
    (bench / "traffic/fixed_poisson.json").write_text(
        json.dumps(dict(SERVE, bag=FIXED)))
    (bench / "traffic/fixed_b16.json").write_text(
        json.dumps(dict(TRAIN, bag=FIXED)))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tinyhet", "source": "test",
                           "file": "bench/configs/tinyhet.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [
        {"name": "tinyhet.serve", "config": "tinyhet",
         "traffic": "fixed_poisson", "chips": 1, "why": "test"},
        {"name": "tinyhet.train", "config": "tinyhet",
         "traffic": "fixed_b16", "chips": 1, "why": "test"}]
    for m in man["end_to_end"] + man["per_layer"]:
        w = m.get("workloads", [])
        if "tiny.serve" in w:
            w.append("tinyhet.serve")
        if "tiny.train" in w:
            w.append("tinyhet.train")
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=2))


def _files(d):
    return {p.relative_to(d): p.read_bytes() for p in sorted(d.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("cell", ["tinyhet.serve", "tinyhet.train"])
def test_a_model_is_added_by_new_files_alone(tmp_path, capsys, cell):
    root = tiny_checkout(tmp_path)
    _add_model(root)
    assert manifest.cell(cell, False, root).model.__file__ == str(
        root / "bench/models/dot_np.py")
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0"], require_chip=False, root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    if cell == "tinyhet.serve":
        assert "dot_np: reference of" in out.err
        assert line["checks"]["ctr_max_abs_err"]["value"] < 1e-6
    else:
        assert set(line["checks"]) == {"first_loss_rel_gap",
                                       "row_grad_gap", "change_norm_gap"}
    # no file the benchmark already had differs from the repository's
    repo, ours = _files(BENCH), _files(root / "bench")
    assert {p: ours[p] for p in repo} == repo
