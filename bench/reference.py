"""The plain reference: DLRM in float32 ``jax.numpy``, sharing no code with
the program under test.

* ``ctr`` — the served forward: gather each bag's rows and sum them, bottom
  MLP, pairwise dots of the bottom output and the bag sums (lower triangle),
  top MLP, sigmoid. Requests go through in blocks of a fixed shape.
* ``train`` — the first steps of the program's training rule: BCE loss, the
  arena's gradient taken densely, row-wise Adagrad on the arena and AdamW on
  the MLPs, with the hyper-parameters the configuration file states.

``precision`` is how matmuls contract: ``"highest"`` (full float32, as the
configuration states) or ``"high"``, three bfloat16 passes written out by
hand so that the control computes the same on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    # rounds to bfloat16 and stays float32; unlike a pair of converts, XLA
    # never folds it away, so the passes below hold what they say
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _three_pass(eq, a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    e = functools.partial(jnp.einsum, eq, precision=HIGHEST)
    return e(ah, bh) + (e(ah, bl) + e(al, bh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dot_high(eq, a, b):
    return _three_pass(eq, a, b)


def _dot_high_fwd(eq, a, b):
    return _three_pass(eq, a, b), (a, b)


def _dot_high_bwd(eq, res, g):
    a, b = res
    ins, out = eq.split("->")
    ia, ib = ins.split(",")
    return (_three_pass(f"{out},{ib}->{ia}", g, b),
            _three_pass(f"{ia},{out}->{ib}", a, g))


_dot_high.defvjp(_dot_high_fwd, _dot_high_bwd)


def dot(eq: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "high":
        return _dot_high(eq, a, b)
    raise ValueError(f"unknown precision {precision!r}")


def mlp(layers, x, precision):
    for i, (w, b) in enumerate(layers):
        x = dot("bi,io->bo", x, w, precision) + b
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def logits(params, dense, emb, precision):
    """dense (B, F0), emb (B, T, D) bag sums -> logits (B,)."""
    bot = mlp(params["bottom"], dense, precision)
    feats = jnp.concatenate([bot[:, None, :], emb], axis=1)
    z = dot("bfd,bgd->bfg", feats, feats, precision)
    li, lj = np.tril_indices(feats.shape[1], -1)
    x = jnp.concatenate([bot, z[:, li, lj]], axis=-1)
    return mlp(params["top"], x, precision)[:, 0]


def bag_sums(arena, ids, bag, n_bags, n_tables, rows):
    """Sum the rows of each bag. ``bag`` (N,) names each position's bag in
    (request, table) order; position ``p`` reads row ``ids[p]`` of table
    ``bag[p] % n_tables``. A position whose bag is ``n_bags`` is padding."""
    flat = ids + (bag % n_tables) * rows
    g = jnp.take(arena, flat, axis=0)
    return jax.ops.segment_sum(g, bag, num_segments=n_bags + 1)[:n_bags]


def _bce(lg, labels):
    return -jnp.mean(labels * jax.nn.log_sigmoid(lg)
                     + (1 - labels) * jax.nn.log_sigmoid(-lg))


@functools.partial(jax.jit, static_argnames=("t", "rows", "precision"))
def _ctr_block(params, dense, ids, bag, *, t, rows, precision):
    n_bags = dense.shape[0] * t
    emb = bag_sums(params["arena"], ids, bag, n_bags, t, rows)
    return jax.nn.sigmoid(logits(params, dense, emb.reshape(
        dense.shape[0], t, -1), precision))


def positions(lens: np.ndarray, offsets: np.ndarray, sel: np.ndarray):
    """Flat positions and local bag numbers of requests ``sel``."""
    t = lens.shape[1]
    bl = lens[sel].reshape(-1).astype(np.int64)
    starts = offsets[(sel[:, None] * t + np.arange(t)).reshape(-1)]
    first = np.repeat(np.cumsum(bl) - bl, bl)
    pos = np.repeat(starts, bl) + (np.arange(int(bl.sum())) - first)
    return pos, np.repeat(np.arange(bl.size), bl)


# positions one block of the served forward gathers at most
BLOCK_POSITIONS = 1 << 21


def ctr(c: dict, params, bodies, sel, precision: str = "highest",
        max_l: int = None) -> np.ndarray:
    """Reference CTRs of requests ``sel`` of ``bodies`` (a
    ``loadgen.Bodies``), in blocks of one fixed shape."""
    t, rows = c["n_tables"], c["rows_per_table"]
    max_l = max_l or int(bodies.lens.max())
    block = min(max(8, BLOCK_POSITIONS // (t * max_l)),
                1 << max(3, int(len(sel) - 1).bit_length()))
    out = []
    for s in range(0, len(sel), block):
        part = np.asarray(sel[s:s + block])
        pos, bag = positions(bodies.lens, bodies.offsets, part)
        k = len(part)
        n = block * t * max_l
        ids = np.zeros(n, np.int32)
        bags = np.full(n, block * t, np.int32)
        ids[:pos.size] = bodies.ids[pos]
        bags[:pos.size] = bag
        dense = np.zeros((block, bodies.dense.shape[1]), np.float32)
        dense[:k] = bodies.dense[part]
        p = _ctr_block(params, dense, ids, bags, t=t, rows=rows,
                       precision=precision)
        out.append(np.asarray(p)[:k])
    return np.concatenate(out) if out else np.zeros(0, np.float32)


# -- training ----------------------------------------------------------------

def leaves(tree) -> list:
    """The arena, then each MLP layer's weight and bias, bottom first."""
    out = [tree.get("arena")]
    for part in ("bottom", "top"):
        for w, b in tree[part]:
            out += [w, b]
    return out


def _loss(params, batch, *, t, rows, precision):
    b = batch["dense"].shape[0]
    emb = bag_sums(params["arena"], batch["ids"], batch["bag"], b * t, t,
                   rows)
    lg = logits(params, batch["dense"], emb.reshape(b, t, -1), precision)
    return _bce(lg, batch["labels"])


@functools.partial(jax.jit, static_argnames=("t", "rows", "precision", "opt"),
                   donate_argnums=(0, 1))
def _step(params, state, batch, *, t, rows, precision, opt):
    (a_lr, a_eps), (lr, b1, b2, eps, wd) = opt
    loss, g = jax.value_and_grad(_loss)(params, batch, t=t, rows=rows,
                                        precision=precision)
    acc = state["acc"] + jnp.mean(jnp.square(g["arena"]), -1, keepdims=True)
    arena = params["arena"] - a_lr * g["arena"] / (jnp.sqrt(acc) + a_eps)
    step = state["step"] + 1
    tf = step.astype(jnp.float32)
    mlp_p = {k: params[k] for k in ("bottom", "top")}
    mlp_g = {k: g[k] for k in ("bottom", "top")}
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, state["m"], mlp_g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, state["v"],
                     mlp_g)

    def upd(p, m_, v_):
        mh, vh = m_ / (1 - b1 ** tf), v_ / (1 - b2 ** tf)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)

    new = jax.tree.map(upd, mlp_p, m, v)
    new["arena"] = arena
    norms = [jnp.sqrt(jnp.sum(jnp.square(x))) for x in leaves(g)]
    return new, {"acc": acc, "m": m, "v": v, "step": step}, loss, norms


def _opt(c):
    a, m = c["optimizer"]["arena"], c["optimizer"]["mlp"]
    return ((a["lr"], a["eps"]),
            (m["lr"], m["b1"], m["b2"], m["eps"], m["weight_decay"]))


def train_batch_of(bodies, max_l: int) -> dict:
    """A batch of the reference's own format: each position tagged with its
    bag, padded to one static length with positions of no bag."""
    n, t = bodies.lens.shape
    ids = np.zeros(n * t * max_l, np.int32)
    bag = np.full(ids.size, n * t, np.int32)
    ids[:bodies.ids.size] = bodies.ids
    bag[:bodies.ids.size] = np.repeat(np.arange(n * t),
                                      bodies.lens.reshape(-1))
    return {"dense": bodies.dense, "labels": bodies.labels, "ids": ids,
            "bag": bag}


def touched_rows(batch: dict, n_tables: int, rows: int) -> np.ndarray:
    """The arena rows that ``batch`` reads, each once, in order."""
    n_bags = batch["dense"].shape[0] * n_tables
    real = batch["bag"] < n_bags
    flat = (batch["ids"][real].astype(np.int64)
            + (batch["bag"][real] % n_tables).astype(np.int64) * rows)
    return np.unique(flat)


def row_norms(acc, rows: np.ndarray, dim: int) -> np.ndarray:
    """||g|| of each of ``rows`` from a row-wise Adagrad accumulator after
    one step, which holds mean(g_row^2)."""
    a = np.asarray(jnp.take(acc[:, 0], jnp.asarray(rows)), np.float64)
    return np.sqrt(dim * a)


def half_batch(batch: dict, n_tables: int) -> dict:
    """``batch`` with its second half of requests left out: the fault of a
    step that takes the mean over half its batch."""
    n = batch["dense"].shape[0] // 2
    bag = np.where(batch["bag"] < n * n_tables, batch["bag"], n * n_tables)
    return {"dense": batch["dense"][:n], "labels": batch["labels"][:n],
            "ids": batch["ids"], "bag": bag.astype(np.int32)}


def change_norms(c: dict, seed: int, params) -> list:
    """Per leaf, the norm of ``params`` minus the seed's initial weights;
    the arena is remade table by table so two arenas are never held."""
    t, rows = c["n_tables"], c["rows_per_table"]
    arena = params["arena"]
    sq = 0.0
    for i in range(t):
        sq += float(_sq_diff(arena, weights.table(c, seed, i), i * rows))
    sq += float(jnp.sum(jnp.square(arena[t * rows:])))
    init = weights.make_mlp(c, seed)
    out = [np.sqrt(sq)]
    for x, x0 in zip(leaves(params)[1:], leaves(init)[1:]):
        out.append(float(jnp.sqrt(jnp.sum(jnp.square(x - x0)))))
    return out


@jax.jit
def _sq_diff(arena, table, start):
    part = jax.lax.dynamic_slice_in_dim(arena, start, table.shape[0])
    return jnp.sum(jnp.square(part - table))


def train(c: dict, seed: int, batches, precision: str = "highest",
          grad_rows: np.ndarray = None) -> dict:
    """The reference's first ``len(batches)`` steps from the seed's weights:
    each step's loss, each leaf's first gradient norm, the first gradient's
    norm on each arena row of ``grad_rows`` (by default those the first
    batch reads), each leaf's change."""
    t, rows = c["n_tables"], c["rows_per_table"]
    params = weights.make(c, seed)
    mlp_p = {k: params[k] for k in ("bottom", "top")}
    zeros = lambda: jax.tree.map(jnp.zeros_like, mlp_p)
    state = {"acc": jnp.zeros((params["arena"].shape[0], 1), jnp.float32),
             "m": zeros(), "v": zeros(), "step": jnp.zeros((), jnp.int32)}
    losses, grad_norms, row_grads = [], None, None
    for b in batches:
        params, state, loss, norms = _step(
            params, state, {k: jnp.asarray(v) for k, v in b.items()},
            t=t, rows=rows, precision=precision, opt=_opt(c))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = [float(x) for x in norms]
            if grad_rows is None:
                grad_rows = touched_rows(batches[0], t, rows)
            row_grads = row_norms(state["acc"], grad_rows, c["emb_dim"])
    del state
    return {"losses": losses, "grad_norms": grad_norms,
            "row_grad_norms": row_grads,
            "change_norms": change_norms(c, seed, params)}
