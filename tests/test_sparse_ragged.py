"""Property + coverage tests: ragged sparse path, sharding, hot-row cache.

The sharded variants run WITHOUT a mesh by vmapping over the shard axis
with a named axis — axis_index/psum behave exactly as under shard_map, so
the ownership/masking protocol is exercised on a 1-device CPU. (The real
shard_map path is covered in test_distributed.py with 8 fake devices.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.kernels import embedding_gather as eg
from repro.kernels import ref as kref


def _ragged_case(rng, spec, b, max_l, pad=0):
    """Random ragged batch with the hard edges forced in: an empty bag, a
    full-length bag, plus null-row and padded-row targeting indices."""
    n_bags = b * spec.n_tables
    lens = rng.randint(0, max_l + 1, n_bags).astype(np.int32)
    lens[0] = 0
    lens[-1] = max_l
    off = np.zeros(n_bags + 1, np.int32)
    np.cumsum(lens, out=off[1:])
    n = int(off[-1])
    idx = rng.randint(0, spec.rows_per_table, n + pad).astype(np.int32)
    return jnp.asarray(idx), jnp.asarray(off), lens


def _oracle(arena, spec, idx, off):
    a = np.asarray(arena)
    idx = np.asarray(idx)
    off = np.asarray(off)
    n_bags = len(off) - 1
    b = n_bags // spec.n_tables
    out = np.zeros((b, spec.n_tables, spec.dim), np.float32)
    for k in range(n_bags):
        t = k % spec.n_tables
        for p in range(off[k], off[k + 1]):
            out[k // spec.n_tables, t] += a[idx[p]
                                            + t * spec.rows_per_table]
    return out


# ---------------------------------------------------------------------------
# sparse_lengths_sum kernel vs jnp oracle (hypothesis-generated offsets)
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=12)
@given(st.integers(1, 5), st.integers(1, 5), st.integers(0, 2**31 - 1))
def test_sls_kernel_vs_oracle_property(n_bags, max_l, seed):
    """Pallas ragged kernel (interpret) == jnp oracle over random ragged
    offsets including empty bags, full max_l bags, single-bag batches."""
    rng = np.random.RandomState(seed % (2**32 - 1))
    v, d = 32, 8
    table = jnp.asarray(rng.randn(v, d), jnp.float32)
    lens = rng.randint(0, max_l + 1, n_bags).astype(np.int32)
    if n_bags > 1:
        lens[0] = 0           # empty bag
        lens[-1] = max_l      # full bag
    off = np.zeros(n_bags + 1, np.int32)
    np.cumsum(lens, out=off[1:])
    idx = rng.randint(0, v, max(int(off[-1]), 1)).astype(np.int32)
    got = eg.sparse_lengths_sum(table, jnp.asarray(idx), jnp.asarray(off),
                                max_l=max_l, interpret=True)
    want = kref.sparse_lengths_sum(table, jnp.asarray(idx),
                                   jnp.asarray(off))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and the oracle against a python loop, so both impls are anchored
    manual = np.zeros((n_bags, d), np.float32)
    for k in range(n_bags):
        for p in range(off[k], off[k + 1]):
            manual[k] += np.asarray(table)[idx[p]]
    np.testing.assert_allclose(np.asarray(want), manual, rtol=1e-5,
                               atol=1e-5)


def test_sls_single_bag_and_all_empty():
    table = jnp.asarray(np.arange(24, dtype=np.float32).reshape(6, 4))
    # single bag holding everything
    got = eg.sparse_lengths_sum(table, jnp.asarray([0, 2, 5], jnp.int32),
                                jnp.asarray([0, 3], jnp.int32), max_l=3,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0],
                               np.asarray(table)[[0, 2, 5]].sum(0))
    # every bag empty -> all zeros (the pipeline's dummy tail stream)
    got = eg.sparse_lengths_sum(table, jnp.asarray([0, 0], jnp.int32),
                                jnp.asarray([0, 0, 0], jnp.int32), max_l=2,
                                interpret=True)
    assert np.abs(np.asarray(got)).max() == 0.0


# ---------------------------------------------------------------------------
# fixed-path sharding property: lookup_sharded == lookup, shards {1,2,4}
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=9)
@given(st.sampled_from([1, 2, 4]), st.integers(0, 2**31 - 1))
def test_lookup_sharded_matches_lookup(shards, seed):
    rng = np.random.RandomState(seed % (2**32 - 1))
    spec = se.ArenaSpec(3, 30, 8)
    arena = se.init_arena(jax.random.PRNGKey(seed % 997), spec, shards)
    idx = rng.randint(0, spec.rows_per_table, (2, 3, 4)).astype(np.int32)
    # force the edge rows in: null row and the last padded row, expressed
    # as per-table ids (flatten adds t*rows_per_table back)
    idx[0, 0, 0] = spec.null_row                      # table 0: base 0
    idx[0, 1, 0] = spec.null_row - spec.rows_per_table
    idx[1, 2, 0] = (spec.padded_rows(shards) - 1
                    - 2 * spec.rows_per_table)        # padded (zero) row
    idx = jnp.asarray(idx)

    want = es.lookup_fixed(es.FpArena(arena), spec, idx)
    flat = se.flatten_indices(spec, idx)
    shard_view = jnp.reshape(arena, (shards, -1, spec.dim))
    outs = jax.vmap(
        lambda a: es.FpArena(a).shard_reduce_fixed(spec, flat, "x")
        .reshape(idx.shape[0], idx.shape[1], spec.dim)
        .astype(arena.dtype), axis_name="x")(shard_view)
    for s in range(shards):
        np.testing.assert_allclose(np.asarray(outs[s]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ragged path: replicated == sharded == oracle; quantized bound
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=9)
@given(st.sampled_from([1, 2, 4]), st.integers(0, 2**31 - 1))
def test_lookup_ragged_sharded_matches_unsharded(shards, seed):
    rng = np.random.RandomState(seed % (2**32 - 1))
    spec = se.ArenaSpec(3, 30, 8)
    arena = se.init_arena(jax.random.PRNGKey(seed % 997), spec, shards)
    idx, off, _ = _ragged_case(rng, spec, b=3, max_l=4, pad=5)

    want = es.lookup_bags(es.FpArena(arena), spec, idx, off, max_l=4)
    np.testing.assert_allclose(np.asarray(want),
                               _oracle(arena, spec, idx, off),
                               rtol=1e-5, atol=1e-5)
    flat = se.flatten_ragged_indices(spec, idx, off)
    n_bags = off.shape[0] - 1
    shard_view = jnp.reshape(arena, (shards, -1, spec.dim))
    outs = jax.vmap(
        lambda a: es.FpArena(a).shard_reduce_flat(spec, flat, off, "x")
        .reshape(n_bags // spec.n_tables, spec.n_tables, spec.dim)
        .astype(arena.dtype), axis_name="x")(shard_view)
    for s in range(shards):
        np.testing.assert_allclose(np.asarray(outs[s]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_lookup_ragged_quantized_error_bound(rng):
    spec = se.ArenaSpec(2, 50, 16)
    arena = se.init_arena(jax.random.PRNGKey(0), spec, scale=1.0)
    q, scales = se.quantize_arena(arena)
    idx, off, _ = _ragged_case(rng, spec, b=4, max_l=6, pad=3)
    exact = es.lookup_bags(es.FpArena(arena), spec, idx, off, max_l=6)
    approx = es.lookup_bags(es.QuantizedArena(q, scales), spec, idx, off,
                            max_l=6)
    bound = 6 * float(scales.max()) + 1e-6
    assert float(jnp.abs(exact - approx).max()) <= bound


def test_flatten_ragged_routes_padding_to_null(rng):
    spec = se.ArenaSpec(2, 10, 4)
    idx = jnp.asarray([3, 7, 5, 9, 1, 1, 1], jnp.int32)   # 3 padded
    off = jnp.asarray([0, 2, 4], jnp.int32)
    flat = np.asarray(se.flatten_ragged_indices(spec, idx, off))
    np.testing.assert_array_equal(flat[:4], [3, 7, 15, 19])
    assert (flat[4:] == spec.null_row).all()


# (n_tables, samples, max_l, bag lengths or None for random ones, padded
# tail): the edges of the relayout the default reduce_bags runs
_RELAYOUT_CASES = {
    "t1_empty_runs": (1, 9, 4, [0, 0, 3, 4, 0, 0, 0, 2, 0], 5),
    "t1_all_empty": (1, 4, 3, [0, 0, 0, 0], 4),
    "t1_empty_stream": (1, 3, 2, [0, 0, 0], 0),
    "t50_random_runs": (50, 2, 6, None, 7),
    "t50_all_full": (50, 1, 3, [3] * 50, 0),
    "t50_long_tail": (50, 1, 4, None, 260),
}


def _relayout_case(name):
    t, b, max_l, lens, pad = _RELAYOUT_CASES[name]
    rng = np.random.RandomState(len(name))
    spec = se.ArenaSpec(t, 20, 8)
    if lens is None:
        lens = rng.randint(0, max_l + 1, b * t)
        lens[3:7] = 0                 # a run of empty bags
        lens[10] = max_l              # a full bag
        lens[-1] = 0                  # a trailing empty bag
    off = np.r_[0, np.cumsum(lens)].astype(np.int32)
    idx = rng.randint(0, spec.rows_per_table,
                      int(off[-1]) + pad).astype(np.int32)
    return spec, idx, off, max_l


def _old_order_dense(spec, idx, off, max_l):
    """numpy oracle of the relayout's old order: each stream position's
    bag by binary search, that bag's table base added, then the
    (n_bags, max_l) relayout with every empty slot at the null row."""
    n_bags = len(off) - 1
    seg = np.searchsorted(off[1:], np.arange(len(idx)), side="right")
    table = np.minimum(seg, n_bags - 1) % spec.n_tables
    flat = np.where(seg < n_bags, idx + table * spec.rows_per_table,
                    spec.null_row).astype(idx.dtype)
    dense = np.full((n_bags, max_l), spec.null_row, idx.dtype)
    for k in range(n_bags):
        run = flat[off[k]:off[k + 1]]
        dense[k, :len(run)] = run
    return dense


def _relayout_source(kind, arena, spec, idx, off):
    if kind == "fp":
        return es.FpArena(arena)
    if kind == "int8":
        return es.QuantizedArena.from_arena(arena)
    counts = se.trace_row_counts(spec, jnp.asarray(idx), jnp.asarray(off))
    cache = se.build_hot_cache(arena, spec, counts, 8)
    return es.CachedSource(cache, es.FpArena(arena),
                           coherent=kind == "cached_coherent")


@pytest.mark.parametrize("kind", ["fp", "int8", "cached_coherent",
                                  "cached_incoherent"])
@pytest.mark.parametrize("case", sorted(_RELAYOUT_CASES))
def test_dense_arena_ids_match_the_searched_order(case, kind):
    """Taking each bag's table from its row of the dense id matrix gives
    the same arena ids as searching each position's bag and flattening
    first, bit for bit, and so the same lookup through every source that
    inherits the default ``reduce_bags``."""
    spec, idx, off, max_l = _relayout_case(case)
    want = _old_order_dense(spec, idx, off, max_l)
    got = se.dense_arena_ids(spec, jnp.asarray(idx), jnp.asarray(off),
                             max_l=max_l)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)

    arena = se.init_arena(jax.random.PRNGKey(len(case)), spec, scale=1.0)
    src = _relayout_source(kind, arena, spec, idx, off)
    out = es.lookup_bags(src, spec, jnp.asarray(idx), jnp.asarray(off),
                         max_l=max_l)
    oracle = src.reduce_dense(spec, jnp.asarray(want)).reshape(
        out.shape).astype(src.out_dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


# ---------------------------------------------------------------------------
# hot-row cache
# ---------------------------------------------------------------------------

def test_hot_cache_exact_vs_uncached(rng):
    spec = se.ArenaSpec(3, 40, 8)
    arena = se.init_arena(jax.random.PRNGKey(2), spec)
    idx, off, _ = _ragged_case(rng, spec, b=4, max_l=5, pad=4)
    counts = se.trace_row_counts(spec, idx, off)
    for k in (1, 8, 64):
        cache = se.build_hot_cache(arena, spec, counts, k)
        got = es.lookup_bags(es.CachedSource(cache, es.FpArena(arena)),
                             spec, idx, off, max_l=5)
        want = es.lookup_bags(es.FpArena(arena), spec, idx, off, max_l=5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_hot_cache_hit_miss_accounting():
    """Hand-built trace: rows {0, 1} of table 0 hot, others cold."""
    spec = se.ArenaSpec(2, 10, 4)
    arena = se.init_arena(jax.random.PRNGKey(0), spec)
    counts = np.zeros(spec.total_rows)
    counts[0] = 100                        # table 0 row 0
    counts[1] = 50                         # table 0 row 1
    cache = se.build_hot_cache(arena, spec, counts, k=2)
    assert set(np.asarray(cache.hot_ids).tolist()) == {0, 1}
    # bags: table0=[0,1,5] (2 hits, 1 miss), table1=[0] (arena row 10: miss)
    idx = jnp.asarray([0, 1, 5, 0], jnp.int32)
    off = jnp.asarray([0, 3, 4], jnp.int32)
    hr = float(se.cache_hit_rate(cache, spec, idx, off))
    assert hr == pytest.approx(2 / 4)
    # padded positions must not count as hits or lookups
    idx_p = jnp.asarray([0, 1, 5, 0, 0, 0], jnp.int32)
    assert float(se.cache_hit_rate(cache, spec, idx_p, off)) \
        == pytest.approx(2 / 4)


def test_hot_cache_quantized_cold_bound(rng):
    spec = se.ArenaSpec(2, 30, 8)
    arena = se.init_arena(jax.random.PRNGKey(1), spec, scale=1.0)
    q, scales = se.quantize_arena(arena)
    idx, off, _ = _ragged_case(rng, spec, b=3, max_l=4)
    counts = se.trace_row_counts(spec, idx, off)
    cache = se.build_hot_cache(arena, spec, counts, k=16)
    got = es.lookup_bags(
        es.CachedSource(cache, es.QuantizedArena(q, scales)), spec, idx,
        off, max_l=4)
    want = es.lookup_bags(es.FpArena(arena), spec, idx, off, max_l=4)
    bound = 4 * float(scales.max()) + 1e-6
    assert float(jnp.abs(got - want).max()) <= bound


def test_hot_cache_all_rows_hot_makes_cold_pass_inert(rng):
    """K >= all touched rows: the cold pass reduces only null rows."""
    spec = se.ArenaSpec(2, 12, 4)
    arena = se.init_arena(jax.random.PRNGKey(3), spec)
    idx, off, _ = _ragged_case(rng, spec, b=2, max_l=3)
    counts = se.trace_row_counts(spec, idx, off)
    cache = se.build_hot_cache(arena, spec, counts, k=spec.null_row)
    assert float(se.cache_hit_rate(cache, spec, idx, off)) == 1.0
    got = es.lookup_bags(es.CachedSource(cache, es.FpArena(arena)), spec,
                         idx, off, max_l=3)
    want = es.lookup_bags(es.FpArena(arena), spec, idx, off, max_l=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
