"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number against its limit from the
configuration file. Runs after the program's state is freed."""
from __future__ import annotations

import json
import sys

import numpy as np

from bench import reference, weights

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone under Adam, and is left out of the change
ROUNDOFF_LEAF = 1e-3


def _check(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": float(value), "limit": float(limit)}


def serve(c: dict, seed: int, bodies, prob: np.ndarray, max_l: int) -> tuple:
    """Checks of a serving run and the number of requests that failed:
    |served CTR - reference CTR| of every request served (nan where a
    request got no answer)."""
    params = weights.make(c, seed)
    sel = np.nonzero(np.isfinite(prob))[0]
    err = np.full(prob.shape, np.nan)
    err[sel] = np.abs(prob[sel].astype(np.float64) - reference.ctr(
        c, params, bodies, sel, max_l=max_l).astype(np.float64))
    del params
    limit = c["limits"]["ctr_max_abs_err"]
    unanswered = int(np.sum(~np.isfinite(prob)))
    worst = float(np.nanmax(err)) if np.isfinite(err).any() else np.inf
    failed = unanswered + int(np.sum(err > limit))
    return [_check("unanswered", unanswered, 0),
            _check("ctr_max_abs_err", worst, limit)], failed


def gap(got, want) -> float:
    """The worst leaf's gap between two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / scale))


def row_gap(got, want) -> float:
    """The median row's gap between two norms, against the reference's
    norm of that row, over the rows whose reference norm is not 0."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    real = want > 0
    if not real.any():
        return np.inf
    return float(np.median(np.abs(got[real] - want[real]) / want[real]))


def train_numbers(prog: dict, ref: dict) -> dict:
    """The numbers a training cell can compare; its configuration's limits
    say which it does."""
    losses = np.asarray(prog["losses"], np.float64)
    want = np.asarray(ref["losses"], np.float64)
    loss_gaps = np.abs(losses - want) / np.abs(want)
    g = np.asarray(ref["grad_norms"], np.float64)
    moving = g >= ROUNDOFF_LEAF * np.median(g)
    return {
        "first_loss_rel_gap": float(loss_gaps[0]),
        "loss_rel_gap": float(np.max(loss_gaps)),
        "grad_norm_gap": gap(prog["grad_norms"], ref["grad_norms"]),
        "row_grad_gap": row_gap(prog["row_grad_norms"],
                                ref["row_grad_norms"]),
        "change_norm_gap": gap(np.asarray(prog["change_norms"])[moving],
                               np.asarray(ref["change_norms"])[moving]),
    }


def train(c: dict, seed: int, prog: dict, batches) -> tuple:
    ref = reference.train(c, seed, batches)
    keys = ("losses", "grad_norms", "change_norms")
    print("readings " + json.dumps({k: {"program": prog[k], "reference":
                                        ref[k]} for k in keys}),
          file=sys.stderr)
    nums = train_numbers(prog, ref)
    print("numbers " + json.dumps(nums), file=sys.stderr)
    checks = [_check(k, nums[k], c["limits"][k]) for k in nums
              if k in c["limits"]]
    failed = sum(ch["value"] > ch["limit"] or not np.isfinite(ch["value"])
                 for ch in checks)
    return checks, failed


def passed(checks) -> bool:
    return all(np.isfinite(ch["value"]) and ch["value"] <= ch["limit"]
               for ch in checks)
