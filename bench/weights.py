"""Weights of a DLRM configuration, made from the seed on the device.

One jitted call makes every leaf in the layout the program serves
(``dlrm.init``'s tree: an arena of all tables plus an always-zero null row,
and the bottom and top MLPs as lists of ``(w, b)``). The reference draws the
same weights from the same seed through this module, so it takes nothing that
the program made. Table ``t`` is drawn from its own key, so one table can be
remade alone (``table``) without holding a second arena.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ARENA_SCALE = 0.01      # row values ~ N(0, 0.01^2), as the program's own init
BIAS_SCALE = 0.1        # non-zero biases, so a dropped bias shows


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _keys(key):
    k_arena, k_bot, k_top = jax.random.split(key, 3)
    return k_arena, k_bot, k_top


def _table(k_arena, t, rows: int, dim: int):
    return ARENA_SCALE * jax.random.normal(jax.random.fold_in(k_arena, t),
                                           (rows, dim), jnp.float32)


def _mlp(key, dims):
    layers = []
    for i in range(len(dims) - 1):
        key, kw, kb = jax.random.split(key, 3)
        w = (2.0 / dims[i]) ** 0.5 * jax.random.normal(
            kw, (dims[i], dims[i + 1]), jnp.float32)
        b = BIAS_SCALE * jax.random.normal(kb, (dims[i + 1],), jnp.float32)
        layers.append((w, b))
    return layers


@functools.partial(jax.jit, static_argnames=("shape",))
def _params(key, shape):
    t, rows, dim, dense, bottom, top = shape
    k_arena, k_bot, k_top = _keys(key)
    arena = jnp.concatenate(
        [_table(k_arena, i, rows, dim) for i in range(t)]
        + [jnp.zeros((1, dim), jnp.float32)])
    f = t + 1
    return {"arena": arena,
            "bottom": _mlp(k_bot, (dense,) + bottom),
            "top": _mlp(k_top, (dim + f * (f - 1) // 2,) + top)}


def _shape(c):
    return (c["n_tables"], c["rows_per_table"], c["emb_dim"],
            c["dense_features"], tuple(c["bottom_mlp"]), tuple(c["top_mlp"]))


def make(c: dict, seed: int) -> dict:
    """All weights of configuration ``c`` (its JSON dict) from ``seed``."""
    return _params(seed_key(seed), _shape(c))


@functools.partial(jax.jit, static_argnames=("shape",))
def _mlp_params(key, shape):
    t, _, dim, dense, bottom, top = shape
    _, k_bot, k_top = _keys(key)
    f = t + 1
    return {"bottom": _mlp(k_bot, (dense,) + bottom),
            "top": _mlp(k_top, (dim + f * (f - 1) // 2,) + top)}


def make_mlp(c: dict, seed: int) -> dict:
    """The MLPs alone, equal to theirs in ``make``."""
    return _mlp_params(seed_key(seed), _shape(c))


@functools.partial(jax.jit, static_argnames=("rows", "dim"))
def _table_of(key, t, rows, dim):
    return _table(_keys(key)[0], t, rows, dim)


def table(c: dict, seed: int, t: int) -> jax.Array:
    """Table ``t`` of the arena alone, equal to its rows in ``make``."""
    return _table_of(seed_key(seed), t, c["rows_per_table"], c["emb_dim"])
