"""The least work a DLRM step needs, counted from shapes.

Counts are of the real ids and requests, never of padding, and of the least
traffic the algorithm needs: each id and each row it names read once, each
bag's sum written once, each weight read once per dispatched batch. A
roofline share is then the least time, ``max(flops / peak_flops, bytes /
peak_bytes)``, over the measured time, and cannot pass 100% unless the
measured time leaves work out.
"""
from __future__ import annotations

F32 = 4


def mlp_dims(c: dict) -> tuple:
    f = c["n_tables"] + 1
    top_in = c["emb_dim"] + f * (f - 1) // 2
    return ((c["dense_features"],) + tuple(c["bottom_mlp"]),
            (top_in,) + tuple(c["top_mlp"]))


def mlp_flops(dims) -> int:
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def mlp_params(dims) -> int:
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def interaction_flops(c: dict) -> int:
    """Pairwise dots of the lower triangle, per request."""
    f = c["n_tables"] + 1
    return f * (f - 1) // 2 * 2 * c["emb_dim"]


def head_flops(c: dict) -> int:
    """Bottom MLP, interaction and top MLP of one request."""
    bottom, top = mlp_dims(c)
    return mlp_flops(bottom) + interaction_flops(c) + mlp_flops(top)


def weight_bytes(c: dict) -> int:
    bottom, top = mlp_dims(c)
    return F32 * (mlp_params(bottom) + mlp_params(top))


class Work:
    """FLOPs and HBM bytes, added up over batches or steps."""

    def __init__(self):
        self.flops = 0.0
        self.bytes = 0.0

    def add(self, flops: float, nbytes: float) -> None:
        self.flops += flops
        self.bytes += nbytes

    def least_s(self, peaks: dict) -> float:
        return max(self.flops / peaks["bf16_flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def gather(c: dict, n_ids: int, n_requests: int) -> tuple:
    """(flops, bytes) of summing ``n_ids`` rows into ``n_requests * T``
    bags: each id and row read once, each bag written once."""
    d = c["emb_dim"]
    return (n_ids * d,
            n_ids * (d * F32 + F32) + n_requests * c["n_tables"] * d * F32)


def head(c: dict, n_requests: int) -> tuple:
    """(flops, bytes) of the dense head over one batch of ``n_requests``:
    the weights once, each request's dense features, bag sums and CTR."""
    per_req = F32 * (c["dense_features"] + c["n_tables"] * c["emb_dim"] + 1)
    return (n_requests * head_flops(c),
            weight_bytes(c) + n_requests * per_req)


def serve_batch(c: dict, n_ids: int, n_requests: int) -> dict:
    return {"gather": gather(c, n_ids, n_requests),
            "head": head(c, n_requests)}


def train_step(c: dict, n_ids: int, n_unique: int, batch: int) -> dict:
    """One sparse training step: the forward gather; the head forward and
    backward (three times the forward's FLOPs); the bag gradients and ids
    read again for the row gradients; each touched row and its Adagrad
    accumulator read and written; AdamW reading weights, moments and
    writing them back."""
    d = c["emb_dim"]
    g_flops, g_bytes = gather(c, n_ids, batch)
    p = weight_bytes(c) // F32
    return {
        "gather": (g_flops, g_bytes),
        "head": (3 * batch * head_flops(c),
                 F32 * p + batch * F32 * (c["dense_features"] + 1)),
        "update": (n_ids * d + n_unique * 4 * d,
                   n_ids * F32 + batch * c["n_tables"] * d * F32
                   + n_unique * 2 * (d * F32 + F32) + 6 * F32 * p),
    }
