"""The trace reduction on a recorded chip trace and on made-up intervals.

``bench/testdata/serve_dlrm6.*`` is a profile of two served dlrm6 batches
(128 requests, then 8) on a TPU v5 lite, inside a ``window`` annotation with
the harness's ``dispatch`` and ``settle`` annotations, and the compiled HLO
text of that serve step.
"""
import gzip

import numpy as np
import pytest

from bench import readers, xplane
from bench.tests.helpers import BENCH

DATA = BENCH / "testdata"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    pb = d / "serve.xplane.pb"
    pb.write_bytes(gzip.decompress(
        (DATA / "serve_dlrm6.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress(
        (DATA / "serve_dlrm6.hlo.txt.gz").read_bytes()).decode()
    return xplane.load(str(pb), [hlo])


def _grid_union(starts, ends, lo, hi, step=1e-8):
    """Busy time by brute force on a fine grid."""
    grid = np.zeros(int(np.ceil((hi - lo) / step)) + 1, bool)
    for s, e in zip(starts, ends):
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(round((a - lo) / step)):int(round((b - lo) / step))] = 1
    return grid.sum() * step


def test_busy_time_is_the_union_of_op_intervals(recorded):
    s = recorded
    hi = float(s.ends.max())
    busy = s.busy_s(0.0, hi)
    assert 0 < busy < hi
    # ops inside a loop overlap the loop: a sum would count them twice
    assert busy < float(np.sum(s.ends - s.starts))
    assert busy == pytest.approx(_grid_union(s.starts, s.ends, 0.0, hi),
                                 abs=2e-8 * len(s.names))


def test_scopes_attribute_the_kernels(recorded):
    s = recorded
    hi = float(s.ends.max())
    assert s.mapped_share > 0.9
    gather = s.names.index("%fused_segment_sum.1")
    assert "/sparse_lookup/emb_lookup/" in s.scopes[gather]
    busy = s.busy_s(0.0, hi)
    g = s.scope_s(readers.GATHER, 0.0, hi)
    h = s.scope_s(readers.HEAD, 0.0, hi)
    assert 0.5 * busy < g < busy and 0 < h < 0.2 * busy
    assert s.outside_s(readers.STAGES, 0.0, hi) == pytest.approx(
        busy - s.scope_s(readers.STAGES, 0.0, hi))
    assert s.top_ops(1)[0][0] == "%fused_segment_sum.1"


def test_idle_gaps_are_named_by_the_host(recorded):
    s = recorded
    hi = float(s.ends.max())
    assert {n for _, _, n in s.host} == {"dispatch", "settle"}
    gaps = s.idle_gaps(1000, hi)
    assert sum(g for _, g in gaps) == pytest.approx(hi - s.busy_s(0.0, hi))
    assert gaps[0][0] in ("dispatch", "settle")
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert len(s.idle_gaps(3, hi)) == 3


def made_up():
    # a loop [0, 4) holding two ops, a head op [5, 6), an op of no scope
    # [6.5, 7); host: dispatch over [0, 4.5), settle over [4.5, 10)
    return xplane.Summary(
        starts=np.array([0.0, 1.0, 2.0, 5.0, 6.5]),
        ends=np.array([4.0, 2.5, 3.0, 6.0, 7.0]),
        names=["%while", "%a", "%b", "%gemm", "%copy"],
        scopes=["f/sparse_lookup/emb_lookup/while",
                "f/sparse_lookup/emb_lookup/x", "f/sparse_lookup_x/y",
                "f/mlp/gemm", ""],
        host=[(0.0, 4.5, "dispatch"), (4.5, 10.0, "settle")])


def test_made_up_intervals():
    s = made_up()
    assert s.busy_s(0.0, 10.0) == pytest.approx(5.5)
    assert s.busy_s(1.5, 5.5) == pytest.approx(3.0)
    # nesting counts once; a scope matches whole path segments only
    assert s.scope_s(readers.GATHER, 0.0, 10.0) == pytest.approx(4.0)
    assert s.scope_s(readers.HEAD, 0.0, 10.0) == pytest.approx(1.0)
    assert s.outside_s(readers.STAGES, 0.0, 10.0) == pytest.approx(0.5)
    assert s.idle_gaps(5, 10.0) == [["settle", 3.0], ["dispatch", 1.0],
                                    ["settle", 0.5]]
    assert s.top_ops(2) == [["%while", 4.0], ["%a", 1.5]]
