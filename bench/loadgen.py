"""Traffic from a mix file and a seed.

Every mix fixes the *shape* of its work from its own ``shape_seed``: the set
of inter-arrival gaps and the set of bag lengths. A run's ``--seed`` only
permutes those sets and draws the ids, dense features and labels, so every
seed offers the same amount of work in another order.

A mix's ``bag`` is ``{"dist": "poisson", "mean", "min", "max"}``, lengths
drawn alike for every table, or ``{"dist": "fixed", "lengths": [...]}``,
one length per table in every request (the multi-hot shape of MLPerf's
DLRM).

Ids are Zipf(alpha)-ranked and folded onto a table's rows, rank ``r`` to row
``(r - 1) % rows``, as ``benchmarks/loadgen.zipf_requests`` draws them. Table
``t`` has ``table_rows[t]`` rows where the configuration gives that key, and
``rows_per_table`` otherwise. Bodies are made in bulk with numpy, not
request by request.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose)."""
    return np.random.default_rng([int(seed), int(stream)])


def poisson_arrivals(rate_qps: float, seconds: float, shape_seed: int,
                     seed: int) -> np.ndarray:
    """Arrival times (s) of a Poisson process at ``rate_qps``, enough to
    cover ``seconds``: a fixed set of exponential gaps, in the seed's
    order."""
    mean = rate_qps * seconds
    n = int(mean + 6 * mean ** 0.5) + 64
    gaps = rng(shape_seed, 1).exponential(1.0 / rate_qps, size=n)
    return np.cumsum(rng(seed, 1).permutation(gaps))


def bag_lengths(n: int, t: int, bag: dict, shape_seed: int,
                seed: int) -> np.ndarray:
    """(n, t) lengths of ``n`` requests' bags in ``t`` tables. Poisson:
    ~ Poisson(mean) clipped to [min, max], a fixed set in the seed's order.
    Fixed: each table's own length in every request."""
    if bag["dist"] == "fixed":
        if len(bag["lengths"]) != t:
            raise ValueError(f"the mix gives {len(bag['lengths'])} bag "
                             f"lengths for {t} tables")
        return np.tile(np.asarray(bag["lengths"], np.int32), (n, 1))
    if bag["dist"] != "poisson":
        raise ValueError(f"unknown bag distribution {bag['dist']!r}")
    lens = np.clip(rng(shape_seed, 2).poisson(bag["mean"], size=n * t),
                   bag["min"], bag["max"]).astype(np.int32)
    return rng(seed, 2).permutation(lens).reshape(n, t)


def max_len(bag: dict) -> int:
    """The longest bag a mix can send."""
    if bag["dist"] == "fixed":
        return max(bag["lengths"])
    return bag["max"]


def zipf_ids(n: int, alpha: float, rows,
             g: np.random.Generator) -> np.ndarray:
    """``n`` ids folded onto ``rows``: one count, or one per id."""
    return ((g.zipf(alpha, size=n) - 1) % rows).astype(np.int32)


@dataclass
class Bodies:
    """``n`` request bodies in bulk: dense (n, F) f32, lens (n, T) int32,
    ids (sum of lens,) int32 per-table row ids in (request, table) order,
    and offsets (n*T + 1,) into ids."""
    dense: np.ndarray
    lens: np.ndarray
    ids: np.ndarray
    offsets: np.ndarray
    labels: np.ndarray

    @property
    def n(self) -> int:
        return self.dense.shape[0]

    def ids_per_request(self) -> np.ndarray:
        return self.lens.sum(axis=1)


def bodies(c: dict, mix: dict, n: int, seed: int) -> Bodies:
    """``n`` bodies of configuration ``c`` under mix ``mix``."""
    t = c["n_tables"]
    lens = bag_lengths(n, t, mix["bag"], mix["shape_seed"], seed)
    g = rng(seed, 3)
    offsets = np.zeros(n * t + 1, np.int64)
    np.cumsum(lens.reshape(-1), out=offsets[1:])
    # one modulus for all tables where they are alike: the per-position
    # one costs a copy of the whole id stream
    rows = (np.repeat(np.tile(np.asarray(c["table_rows"], np.int64), n),
                      lens.reshape(-1))
            if "table_rows" in c else c["rows_per_table"])
    ids = zipf_ids(int(offsets[-1]), mix["zipf_alpha"], rows, g)
    dense = g.standard_normal((n, c["dense_features"]), np.float32)
    w = rng(mix["shape_seed"], 4).standard_normal(c["dense_features"])
    labels = (g.random(n) < 1.0 / (1.0 + np.exp(-0.5 * dense @ w))
              ).astype(np.float32)
    return Bodies(dense=dense, lens=lens, ids=ids, offsets=offsets,
                  labels=labels)


def train_batch(c: dict, mix: dict, b: Bodies) -> dict:
    """A ragged training batch of the program's format, its index stream
    padded to the mix's static length: ``batch * sum(lengths)`` for fixed
    bags, which every batch fills, ``batch * T * max`` for Poisson bags."""
    bag = mix["bag"]
    pad = mix["batch"] * (sum(bag["lengths"]) if bag["dist"] == "fixed"
                          else c["n_tables"] * bag["max"])
    indices = np.zeros(pad, np.int32)
    indices[:b.ids.size] = b.ids
    return {"dense": b.dense, "indices": indices,
            "offsets": b.offsets.astype(np.int32), "labels": b.labels}
