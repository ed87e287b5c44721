"""The main-path Pallas kernels compile for a TPU v5e at real widths.

The TPU compiler is installed without a chip, and it compiles for a chip
that is only described (``jax.experimental.topologies``). That catches what
interpret mode cannot: blocks that break the (8, 128) tiling, scalar
prefetch that overflows SMEM, and a relayout copy of a multi-GB arena.
Nothing here runs on a chip or says anything about results or speed.

The topology is described inside a fixture only — never at import, in a
``skipif`` or in ``parametrize`` — so that every test worker collects the
same tests and only the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.dlrm import DLRM_CONFIGS
from repro.core import dlrm
from repro.core import embedding_source as es
from repro.kernels import embedding_gather as eg
from repro.kernels import feature_interaction as fi
from repro.kernels import fused_dispatch as fd
from repro.kernels import gemm as gm
from repro.kernels import ops

D = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a described chip's compiles can be written to the persistent cache
    # but never read back, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas():
    ops.set_impl("pallas")
    yield
    ops.set_impl("auto")


def _compile(f, *args):
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (arena rows, bags, max_l): dlrm1 served at its Table I width; dlrm5 at the
# largest training batch (128 samples x 50 tables, bags up to 2 x 80).
WIDTHS = {"dlrm1": (dlrm.arena_spec(DLRM_CONFIGS["dlrm1"]).total_rows,
                    32 * 5, 40),
          "dlrm5": (dlrm.arena_spec(DLRM_CONFIGS["dlrm5"]).total_rows,
                    128 * 50, 160)}


def _row_kernel(name, v, b, l, s):
    """(fn, args, arena bytes) of one row-streaming kernel at (v, b, l)."""
    tab, ids = s((v, D)), s((b, l), jnp.int32)
    if name == "fused_segment_sum":
        return fd.fused_segment_sum, (tab, ids), v * D * 4
    if name == "fused_cached_segment_sum":
        return (fd.fused_cached_segment_sum, (s((4097, D)), tab, ids, ids),
                v * D * 4)
    if name == "fused_int4_segment_sum":
        return (lambda p, sc, i: fd.fused_int4_segment_sum(p, sc, i, dim=D),
                (s((v, D // 2), jnp.uint8), s((v, 1)), ids), v * (D // 2 + 4))
    if name == "embedding_bag":
        return eg.embedding_bag, (tab, ids), v * D * 4
    flat, off = s((b * l,), jnp.int32), s((b + 1,), jnp.int32)
    if name == "sparse_lengths_sum":
        return (lambda t, i, o: eg.sparse_lengths_sum(t, i, o, max_l=l),
                (tab, flat, off), v * D * 4)
    # the backward: its argsort dominates the compile, so a served batch
    # (160 bags x 20) stands in for the stream at either width
    b, l = 160, 20
    return (lambda g, i, o: eg.sls_grad_table(g, i, o, n_rows=v),
            (s((b, D)), s((b * l,), jnp.int32), s((b + 1,), jnp.int32)),
            v * D * 4)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["fused_segment_sum",
                                    "fused_cached_segment_sum",
                                    "fused_int4_segment_sum",
                                    "embedding_bag", "sparse_lengths_sum",
                                    "sls_grad_table"])
def test_row_kernel_compiles_without_relayout(one_chip, kernel, arch):
    """Each gather-family kernel compiles at the arch's width, and the
    arena is read in place: no temporary anywhere near its size (a (V, D)
    table viewed row-major would be copied at 4x its bytes)."""
    v, b, l = WIDTHS[arch]
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    f, args, arena = _row_kernel(kernel, v, b, l, s)
    mem = _compile(f, *args).memory_analysis()
    assert mem.temp_size_in_bytes < arena // 16, (mem, arena)


@pytest.mark.parametrize("k", [47, 1307])
def test_gemm_compiles_for_any_contraction(one_chip, k):
    """K = 1307 (the 50-table top-MLP input) is prime: the contraction is
    zero-padded to whole 128-blocks instead of shrinking the block."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                           sharding=one_chip)
    _compile(gm.gemm, s((128, k)), s((k, 512)))


def test_interaction_compiles_at_51_features(one_chip):
    x = jax.ShapeDtypeStruct((128, 51, D), jnp.float32, sharding=one_chip)
    _compile(fi.interaction, x)


def test_sharded_lookup_compiles_on_four_chips(topo, pallas):
    """dlrm5's arena row-sharded over a 4-way 'model' mesh, served through
    ShardedArena: each chip holds a quarter of the arena, the partial bags
    meet in one all-reduce, and the dense head runs its kernels."""
    cfg = DLRM_CONFIGS["dlrm5"]
    mesh = Mesh(np.array(topo.devices), ("model",))
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(lambda k: dlrm.init(k, cfg, shards=4),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        params)
    params["arena"] = jax.ShapeDtypeStruct(
        params["arena"].shape, jnp.float32,
        sharding=NamedSharding(mesh, P("model", None)))
    b, max_l = 32, 2 * cfg.lookups_per_table
    batch = {"dense": jax.ShapeDtypeStruct((b, cfg.dense_features),
                                           jnp.float32, sharding=rep),
             "indices": jax.ShapeDtypeStruct((b * cfg.n_tables * max_l,),
                                             jnp.int32, sharding=rep),
             "offsets": jax.ShapeDtypeStruct((b * cfg.n_tables + 1,),
                                             jnp.int32, sharding=rep)}
    step = dlrm.make_ragged_serve_step(cfg, max_l=max_l)
    compiled = _compile(
        lambda p, bt: step(p, bt, es.ShardedArena(es.FpArena(p["arena"]),
                                                  mesh)), params, batch)
    assert "all-reduce" in compiled.as_text()
    arena = params["arena"].shape[0] * D * 4
    mem = compiled.memory_analysis()
    assert arena // 4 <= mem.argument_size_in_bytes < arena // 4 + (64 << 20)
