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
import numpy as np

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


def make(c: dict, seed: int, mesh=None) -> dict:
    """All weights of configuration ``c`` (its JSON dict) from ``seed``.
    On a ``mesh``, as the program's sharded paths hold them: the arena
    padded with zero rows to a multiple of the ``model`` axis and
    row-sharded over it, the MLPs replicated. Each chip then draws only
    its own rows, equal bit for bit to theirs in the arena made whole."""
    if mesh is None:
        return _params(seed_key(seed), _shape(c))
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    key = jax.device_put(seed_key(seed), rep)
    return {"arena": _sharded_arena(key, _shape(c), mesh),
            **jax.device_put(_mlp_params(key, _shape(c)), rep)}


def _normal_bits(bits):
    """``jax.random.normal``'s float32 from its 32 random bits."""
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jax.lax.bitcast_convert_type(
        (bits >> 9) | np.uint32(0x3F800000), jnp.float32) - 1.0
    u = jnp.maximum(lo, u * (np.float32(1.0) - lo) + lo)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


def _arena_rows(k_arena, g, t: int, rows: int, dim: int):
    """Rows ``g`` of the arena (global row ids; those past the tables are
    zero). ``jax.random.normal`` draws element ``n`` of a table from the
    threefry hash of the counter ``n`` under the table's key, so a row can
    be drawn alone (JAX's partitionable threefry, its default)."""
    from jax.extend.random import threefry2x32_p
    if (not jax.config.jax_threefry_partitionable or rows * dim >= 2 ** 32
            or t * rows >= 2 ** 31):
        raise ValueError("arena rows are drawn alone only under the "
                         "partitionable threefry, for tables under 2**32 "
                         "elements and arenas under 2**31 rows")
    keys = jax.vmap(lambda i: jax.random.fold_in(k_arena, i))(
        jnp.arange(t))[jnp.minimum(g // rows, t - 1)]
    n = ((g % rows).astype(jnp.uint32)[:, None] * np.uint32(dim)
         + jnp.arange(dim, dtype=jnp.uint32))
    b1, b2 = threefry2x32_p.bind(keys[:, :1], keys[:, 1:],
                                 jnp.zeros_like(n), n)
    x = ARENA_SCALE * _normal_bits(b1 ^ b2)
    return jnp.where((g < t * rows)[:, None], x, 0.0)


@functools.partial(jax.jit, static_argnames=("shape", "mesh", "block"))
def _sharded_arena(key, shape, mesh, block=2 ** 24):
    """The arena row-sharded over ``mesh``, each chip drawing its rows in
    blocks of ``block`` elements, so that no chip holds more than its
    share and one block's scratch."""
    from jax.sharding import PartitionSpec as P
    t, rows, dim = shape[:3]
    local = -(-(t * rows + 1) // mesh.shape["model"])
    block = min(local, max(1, block // dim))

    def body(key):
        k_arena = _keys(key)[0]
        lo = jax.lax.axis_index("model") * local

        def fill(k, out):
            # the last block ends at the shard's end, drawing again a few
            # rows of the one before it
            start = jnp.minimum(k * block, local - block)
            g = lo + start + jnp.arange(block)
            return jax.lax.dynamic_update_slice(
                out, _arena_rows(k_arena, g, t, rows, dim), (start, 0))

        return jax.lax.fori_loop(0, -(-local // block), fill,
                                 jnp.zeros((local, dim), jnp.float32))

    return jax.shard_map(body, mesh=mesh, in_specs=P(),
                         out_specs=P("model", None), check_vma=False)(key)


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
