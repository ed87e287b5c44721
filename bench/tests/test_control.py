"""The control comes out as not correct: the plain reference in the
program's place, its matmuls in three bfloat16 passes, at the widths of each
configuration (fewer rows, so that the CPU holds it) and against the limits
its configuration file states. A training cell's control or its half-batch
fault fails one of the cell's numbers."""
import json

import pytest

from bench import control, manifest
from bench.tests.helpers import ROOT

ROWS = 500


def config(name: str) -> dict:
    c = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    c["rows_per_table"] = ROWS
    return c


def mix(name: str) -> dict:
    return json.loads((ROOT / f"bench/traffic/{name}.json").read_text())


@pytest.mark.parametrize("seed", [2 ** 33 + 1, 5])
def test_serving_control_fails_the_limit(seed):
    """The serving cell's own configuration and mix."""
    c, m = config("dlrm5"), mix("overload_dlrm5")
    got = control.serve_readings(manifest.model(c), c, m, seed,
                                 192 / m["rate_qps"])
    assert got["unanswered"] == 0
    assert got["ctr_max_abs_err"] > c["limits"]["ctr_max_abs_err"]


def test_training_control_and_fault_fail_a_number():
    c, m = config("dlrm5"), dict(mix("closed_b256"), batch=64)
    got = control.train_readings(manifest.model(c), c, m, 2 ** 33 + 3)
    for kind in ("control", "half_batch"):
        assert any(v > c["limits"][k] for k, v in got[kind].items()
                   if k in c["limits"]), kind
