"""The harness end to end on the CPU, past its look for a chip: a tiny
serving cell and a tiny training cell come out correct, and each fault the
timed path can have makes ``correct`` false. Without a TPU, or without the
program beside it, ``bench/run.py`` exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.helpers import ROOT, tiny_checkout

SEED = 2 ** 33 + 17


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("bench"))


def run_cell(root, cell, capsys, seconds=1.0):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", "0"], require_chip=False,
                  root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


def test_serving_cell_is_correct(checkout, capsys):
    line = run_cell(checkout, "tiny.serve", capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 50
    # the CPU reports no peak memory, and the reader then reports nothing
    assert set(line["metrics"]) == {"serve_qps", "setup_s"}
    assert line["metrics"]["serve_qps"]["value"] > 0
    assert line["checks"]["ctr_max_abs_err"]["value"] < 1e-6


def test_training_cell_is_correct(checkout, capsys):
    line = run_cell(checkout, "tiny.train", capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == {"first_loss_rel_gap", "row_grad_gap",
                                   "change_norm_gap"}
    assert line["metrics"]["train_samples_s"]["value"] > 0


def _wrap_train_step(monkeypatch, broken):
    from repro.core import dlrm
    orig = dlrm.make_train_step_ragged

    def factory(*a, **kw):
        opt, step = orig(*a, **kw)
        return opt, lambda p, s, b: broken(step, p, s, b)

    monkeypatch.setattr(dlrm, "make_train_step_ragged", factory)


def _unchanged(step, p, s, b):
    _, _, loss, rows = step(p, s, b)
    return p, s, loss, rows


def _half_batch(step, p, s, b):
    n = b["dense"].shape[0] // 2
    t = (b["offsets"].shape[0] - 1) // b["dense"].shape[0]
    half = dict(b, dense=b["dense"][:n], labels=b["labels"][:n],
                offsets=b["offsets"][:n * t + 1])
    return step(p, s, half)


def _loss_altered(step, p, s, b):
    new_p, new_s, loss, rows = step(p, s, b)
    return new_p, new_s, loss * 1.001, rows


@pytest.mark.parametrize("broken", [_unchanged, _half_batch,
                                    _loss_altered])
def test_training_faults_are_not_correct(checkout, capsys, monkeypatch,
                                         broken):
    _wrap_train_step(monkeypatch, broken)
    line = run_cell(checkout, "tiny.train", capsys)
    assert line["correct"] is False and line["failed"] > 0


def test_an_altered_answer_is_not_correct(checkout, capsys, monkeypatch):
    from repro.core import dlrm
    orig = dlrm.make_ragged_serve_step

    def factory(*a, **kw):
        step = orig(*a, **kw)
        return lambda *args: step(*args).at[0].add(1e-3)

    monkeypatch.setattr(dlrm, "make_ragged_serve_step", factory)
    line = run_cell(checkout, "tiny.serve", capsys)
    assert line["correct"] is False and line["failed"] > 0


def _env():
    return {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp")}


def test_refuses_a_machine_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dlrm5.serve.overload",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "correct" not in p.stdout


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dlrm5.serve.overload",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and "correct" not in p.stdout
