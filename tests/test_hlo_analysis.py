"""Trip-count-aware HLO analyzer tests against exactly-known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis, roofline


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_flops_plain_matmul():
    co = _compile(lambda x, w: x @ w,
                  jax.ShapeDtypeStruct((128, 128), jnp.float32),
                  jax.ShapeDtypeStruct((128, 128), jnp.float32))
    r = hlo_analysis.analyze(co.as_text())
    assert r["flops"] == pytest.approx(2 * 128 ** 3, rel=0.01)


def test_flops_scan_multiplies_trip_count():
    def f(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]
    co = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                  jax.ShapeDtypeStruct((10, 128, 128), jnp.float32))
    r = hlo_analysis.analyze(co.as_text())
    assert r["flops"] == pytest.approx(2 * 128 ** 3 * 10, rel=0.01)
    # XLA's own counter misses the loop — documents why we parse ourselves
    ca = co.cost_analysis()
    if isinstance(ca, list):     # older jax returns one dict per program
        ca = ca[0]
    assert ca["flops"] < r["flops"] / 5


def test_flops_nested_scan():
    def g(x, ws):
        def outer(c, w3):
            return jax.lax.scan(lambda c2, w: (c2 @ w, None), c, w3)[0], None
        return jax.lax.scan(outer, x, ws)[0]
    co = _compile(g, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                  jax.ShapeDtypeStruct((5, 4, 64, 64), jnp.float32))
    r = hlo_analysis.analyze(co.as_text())
    assert r["flops"] == pytest.approx(2 * 64 ** 3 * 20, rel=0.01)


def test_bytes_dominated_by_real_traffic():
    """An elementwise op on N floats should cost ~2*4N bytes, not more
    than a few times that."""
    n = 1 << 20
    co = _compile(lambda x: x * 2.0 + 1.0,
                  jax.ShapeDtypeStruct((n,), jnp.float32))
    r = hlo_analysis.analyze(co.as_text())
    assert 8 * n <= r["bytes"] <= 32 * n


def test_shape_parsing():
    assert hlo_analysis._shape_elems_bytes("f32[16,24]{1,0}") == (384, 1536)
    assert hlo_analysis._shape_elems_bytes("bf16[8]")[1] == 16
    e, b = hlo_analysis._shape_elems_bytes("(f32[4], s32[2])")
    assert (e, b) == (6, 24)
    assert hlo_analysis._shape_elems_bytes("pred[]")[1] == 1


def test_roofline_terms_and_dominance():
    rep = roofline.analyze(flops_per_dev=197e12, bytes_per_dev=0.0,
                           coll_bytes_per_dev=0.0, model_flops=197e12 * 256,
                           chips=256)
    assert rep.t_compute == pytest.approx(1.0)
    assert rep.dominant == "compute"
    assert rep.roofline_fraction == pytest.approx(1.0)
    rep2 = roofline.analyze(1e12, 819e9 * 2, 0.0, 1e12 * 256, 256)
    assert rep2.dominant == "memory"


def test_model_flops_moe_uses_active_params():
    from repro.configs.registry import ARCHS
    from repro.configs.base import TRAIN_4K
    from repro.models import api
    cfg = ARCHS["kimi-k2-1t-a32b"]
    cell = {}

    def f(k):
        vals, specs = api.init(k, cfg)
        cell["specs"] = specs
        return vals
    shapes = jax.eval_shape(f, jax.ShapeDtypeStruct((2,), jnp.uint32))
    total, active = roofline.count_params(shapes, cfg)
    assert total > 0.9e12            # ~1T total
    assert active < 0.05 * total     # top-8 of 384 experts


# ---------------------------------------------------------------------------
# timing-free perf gates for the fused segmented dispatch
# ---------------------------------------------------------------------------

def _sparse_fixture():
    from repro.core import embedding_source as es
    from repro.core import sparse_engine as se
    t, rpt, d, b, max_l = 4, 50, 8, 6, 4
    spec = se.ArenaSpec(t, rpt, d)
    arena = se.init_arena(jax.random.PRNGKey(0), spec, scale=1.0)
    rng = np.random.RandomState(0)
    n = b * t * 3
    idx = jnp.asarray(rng.randint(0, rpt, n), jnp.int32)
    lens = np.minimum(rng.randint(0, max_l + 1, b * t), 3)
    off = jnp.asarray(np.r_[0, np.cumsum(lens)].clip(0, n), jnp.int32)
    return es, se, spec, arena, idx, off, max_l


def test_hlo_gate_fused_forwards_are_scatter_free_one_pass():
    """The structural contract behind the bench numbers, asserted on the
    compiled HLO so it cannot rot into a timing flake:

    - every fused forward (plain / cached / grouped) lowers with ZERO
      scatter ops — the dense relayout replaced the per-table full-stream
      segment scatters;
    - the cached forward is ONE pass: with coherence declared
      (``CachedSource(coherent=True)``, the serving-plan default) the
      XLA lowering collapses to the plain arena reduction, so it
      compiles to the SAME op histogram as the uncached forward — the
      hit test survives only in the backward, where the hot/cold grad
      split is real state;
    - the grouped forward runs one small dense reduction per member (T
      reduces over (B, max_l) blocks) with no dynamic loop, instead of T
      reductions over the full interleaved stream;
    - no forward runs a dynamic loop: the relayout reads each slot's
      table off its row of the dense id matrix instead of searching
      for each stream position's bag."""
    import dataclasses
    es, se, spec, arena, idx, off, max_l = _sparse_fixture()
    t = spec.n_tables

    def fp(a, i, o):
        return es.lookup_bags(es.FpArena(a), spec, i, o, max_l=max_l)

    c_fp = hlo_analysis.count_ops(
        jax.jit(fp).lower(arena, idx, off).compile().as_text())

    counts = se.trace_row_counts(spec, idx, off)
    cache = se.build_hot_cache(arena, spec, counts, k=16)

    def cached(hr, so, a, i, o):
        c2 = dataclasses.replace(cache, hot_rows=hr, slot_of=so)
        return es.lookup_bags(es.CachedSource(c2, es.FpArena(a),
                                              coherent=True), spec,
                              i, o, max_l=max_l)

    c_c = hlo_analysis.count_ops(
        jax.jit(cached).lower(cache.hot_rows, cache.slot_of, arena, idx,
                              off).compile().as_text())

    specs = [se.ArenaSpec(1, spec.rows_per_table, spec.dim)
             for _ in range(t)]
    arenas = [se.init_arena(jax.random.PRNGKey(i + 1), sp, scale=1.0)
              for i, sp in enumerate(specs)]

    def grouped(ars, i, o):
        g = es.TableGroupSource(
            members=tuple(es.FpArena(a) for a in ars),
            specs=tuple(specs))
        return es.lookup_bags(g, g.envelope_spec, i, o, max_l=max_l)

    c_g = hlo_analysis.count_ops(
        jax.jit(grouped).lower(arenas, idx, off).compile().as_text())

    # scatter-free: neither a literal scatter op nor XLA:CPU's serialized
    # lowering of one (a while loop around dynamic-update-slice)
    for name, c in (("fp", c_fp), ("cached", c_c), ("grouped", c_g)):
        assert c.get("scatter", 0) == 0, (name, c)
        assert c.get("dynamic-update-slice", 0) == 0, (name, c)
    # cached == one pass: the coherence-law lowering makes the cached
    # forward compile to the same single reduction and gather count as
    # the uncached forward (the slot translate / hot load are dead code
    # outside the backward and get DCE'd)
    assert c_c.get("reduce", 0) == c_fp.get("reduce", 0) == 1, (c_fp, c_c)
    assert c_c.get("gather", 0) == c_fp.get("gather", 0), (c_fp, c_c)
    # plain and cached: each slot's table is its row of the dense id
    # matrix, so no per-position bag search (a while loop) is left
    assert c_fp.get("while", 0) == 0, c_fp
    assert c_c.get("while", 0) == 0, c_c
    # grouped: per-member dense reductions, no dynamic loop over the
    # stream (the T-full-walk shape lowered with while/scatter)
    assert c_g.get("reduce", 0) == t, c_g
    assert c_g.get("while", 0) == 0, c_g


def test_hlo_gate_backward_still_scatters():
    """Sanity inverse of the forward gate: the training backward IS the
    segment scatter-add (the sparse engine run in reverse), so scatters
    must appear there — proving the forward gate isn't vacuous."""
    es, se, spec, arena, idx, off, max_l = _sparse_fixture()

    def loss(a, i, o):
        return es.lookup_bags(es.FpArena(a), spec, i, o,
                              max_l=max_l).sum()

    co = jax.jit(jax.grad(loss)).lower(arena, idx, off).compile()
    c = hlo_analysis.count_ops(co.as_text())
    # XLA:CPU serializes the scatter-add into a while loop of
    # dynamic-update-slice row updates; either form counts
    assert c.get("scatter", 0) + c.get("dynamic-update-slice", 0) >= 1, c
