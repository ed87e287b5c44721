"""The plain reference against the program it judges, at a small size on
the CPU with the kernels interpreted, and the reference's own invariants."""
import functools
import json

import jax
import numpy as np
import pytest

from bench import loadgen, reference, weights
from bench.tests.helpers import BAG, ROOT, TINY


@pytest.fixture
def interpret():
    from repro.kernels import ops
    ops.set_impl("interpret")
    yield
    ops.set_impl("auto")


def smoke_config() -> dict:
    from repro.configs.dlrm import DLRM_SMOKE as s
    return dict(TINY, name=s.name, n_tables=s.n_tables,
                rows_per_table=s.rows_per_table, emb_dim=s.emb_dim,
                dense_features=s.dense_features,
                bottom_mlp=list(s.bottom_mlp), top_mlp=list(s.top_mlp))


def test_weights_have_the_program_layout():
    from repro.configs.dlrm import DLRM_SMOKE
    from repro.core import dlrm
    c = smoke_config()
    got = weights.make(c, 5)
    want = jax.eval_shape(functools.partial(dlrm.init, cfg=DLRM_SMOKE),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert float(np.abs(np.asarray(got["arena"][-1])).max()) == 0.0


def test_weights_come_from_the_seed_alone():
    c = smoke_config()
    a, b = weights.make(c, 2 ** 40 + 3), weights.make(c, 2 ** 40 + 3)
    other = weights.make(c, 3)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["arena"]),
                              np.asarray(other["arena"]))
    t1 = weights.table(c, 7, 1)
    r = c["rows_per_table"]
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(
        weights.make(c, 7)["arena"][r:2 * r]))


def test_reference_ctr_matches_the_engine(interpret):
    """Served CTRs of RecEngine at DLRM_SMOKE (kernels interpreted) agree
    with the reference to float32 summation order."""
    from repro.configs.dlrm import DLRM_SMOKE
    from repro.serving import RecEngine, RecRequest
    c = smoke_config()
    mix = {"bag": BAG, "shape_seed": 3, "zipf_alpha": 1.05}
    b = loadgen.bodies(c, mix, 24, 9)
    params = weights.make(c, 9)
    eng = RecEngine(DLRM_SMOKE, params, source="ragged", max_l=BAG["max"],
                    max_batch=8, buckets=(8,))
    t = c["n_tables"]
    reqs = [RecRequest(rid=i, dense=b.dense[i], sparse_ids=[
        b.ids[b.offsets[i * t + j]:b.offsets[i * t + j + 1]]
        for j in range(t)]) for i in range(b.n)]
    for k in range(0, len(reqs), 8):
        eng.settle(eng.dispatch(reqs[k:k + 8]))
    got = np.array([r.prob for r in reqs])
    want = reference.ctr(c, params, b, np.arange(b.n), max_l=BAG["max"])
    assert np.max(np.abs(got - want)) < 1e-6


def test_three_pass_control_differs_from_full_precision():
    c = json.loads((ROOT / "bench/configs/dlrm5.json").read_text())
    c["rows_per_table"] = 300
    mix = json.loads((ROOT / "bench/traffic/overload_dlrm5.json").read_text())
    b = loadgen.bodies(c, mix, 64, 4)
    p = weights.make(c, 4)
    sel = np.arange(64)
    max_l = mix["bag"]["max"]
    hi = reference.ctr(c, p, b, sel, "highest", max_l=max_l)
    lo = reference.ctr(c, p, b, sel, "high", max_l=max_l)
    assert 0 < np.max(np.abs(hi - lo)) < 1e-3


def test_high_precision_gradient_is_three_pass():
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    f = lambda prec: jax.grad(
        lambda x: reference.dot("bi,io->bo", x, w, prec).sum())(a)
    hi, lo = np.asarray(f("highest")), np.asarray(f("high"))
    assert 0 < np.max(np.abs(hi - lo)) < 1e-3


def test_train_reference_takes_the_configured_steps():
    c = smoke_config()
    mix = {"bag": BAG, "shape_seed": 3, "zipf_alpha": 1.05, "batch": 8}
    batches = [reference.train_batch_of(loadgen.bodies(c, mix, 8, s),
                                        BAG["max"]) for s in range(3)]
    out = reference.train(c, 11, batches)
    assert len(out["losses"]) == 3 and np.all(np.isfinite(out["losses"]))
    n = len(reference.leaves(weights.make(c, 11)))
    assert len(out["grad_norms"]) == len(out["change_norms"]) == n
    assert min(out["change_norms"]) > 0


def test_row_norms_cover_the_first_gradient_of_the_arena():
    """The rows the first batch reads hold the arena's whole first
    gradient: their norms add up to the arena leaf's norm."""
    c = smoke_config()
    mix = {"bag": BAG, "shape_seed": 3, "zipf_alpha": 1.05, "batch": 8}
    batches = [reference.train_batch_of(loadgen.bodies(c, mix, 8, s),
                                        BAG["max"]) for s in range(2)]
    out = reference.train(c, 11, batches)
    rows = reference.touched_rows(batches[0], c["n_tables"],
                                  c["rows_per_table"])
    assert out["row_grad_norms"].shape == rows.shape
    np.testing.assert_allclose(np.sqrt(np.sum(out["row_grad_norms"] ** 2)),
                               out["grad_norms"][0], rtol=1e-5)
