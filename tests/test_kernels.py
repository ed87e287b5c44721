"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import embedding_gather, feature_interaction, gemm, ops, ref

jax.config.update("jax_enable_x64", False)


def _close(a, b, tol=2e-2):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def _row_call(kernel, *args, split=False, **kw):
    """A row-streaming kernel in interpret mode. ``split`` shrinks the
    SMEM id budget so the batch is walked in many calls (outside jit, so
    no cached one-call trace is reused)."""
    if not split:
        return kernel(*args, interpret=True, **kw)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(embedding_gather, "SMEM_WORDS", 12)
        return kernel.__wrapped__(*args, interpret=True, **kw)


# ---------------------------------------------------------------------------
# GEMM (dense engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (128, 128, 128),
                                   (130, 70, 150), (256, 33, 64),
                                   (1, 512, 1), (16, 131, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_matches_oracle(rng, m, k, n, dtype):
    x = jnp.asarray(rng.randn(m, k), dtype)
    w = jnp.asarray(rng.randn(k, n), dtype)
    got = gemm.gemm(x, w, interpret=True)
    want = ref.gemm(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    _close(got, want, tol)


@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 32), (128, 128, 64)])
def test_gemm_block_shapes(rng, bm, bn, bk):
    x = jnp.asarray(rng.randn(96, 80), jnp.float32)
    w = jnp.asarray(rng.randn(80, 112), jnp.float32)
    got = gemm.gemm(x, w, bm=bm, bn=bn, bk=bk, interpret=True)
    _close(got, ref.gemm(x, w), 1e-5)


# ---------------------------------------------------------------------------
# Embedding gather-reduce (sparse engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v,d,b,l,split", [(100, 32, 4, 1, False),
                                           (1000, 32, 16, 20, False),
                                           (512, 128, 8, 80, False),
                                           (64, 48, 3, 5, False),
                                           (1000, 32, 16, 20, True),
                                           (300, 256, 5, 3, True)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_matches_oracle(rng, v, d, b, l, split, dtype):
    table = jnp.asarray(rng.randn(v, d), dtype)
    idx = jnp.asarray(rng.randint(0, v, (b, l)), jnp.int32)
    got = _row_call(embedding_gather.embedding_bag, table, idx, split=split)
    want = ref.embedding_bag(table, idx)
    _close(got, want, 1e-5 if dtype == jnp.float32 else 5e-2)


def test_embedding_bag_d_blocking(rng):
    table = jnp.asarray(rng.randn(256, 96), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 256, (4, 7)), jnp.int32)
    got = embedding_gather.embedding_bag(table, idx, bd=32, interpret=True)
    _close(got, ref.embedding_bag(table, idx), 1e-5)


def test_gather_rows(rng):
    table = jnp.asarray(rng.randn(128, 16), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 128, (9,)), jnp.int32)
    got = embedding_gather.gather_rows(table, idx, interpret=True)
    _close(got, table[idx], 1e-6)


@settings(deadline=None, max_examples=20)
@given(st.integers(2, 50), st.integers(1, 16), st.integers(1, 12),
       st.integers(0, 2**31 - 1))
def test_embedding_bag_property(v, b, l, seed):
    """Property: gather-reduce is linear in the table and permutation-
    invariant in the lookup order."""
    r = np.random.RandomState(seed % (2**32 - 1))
    table = jnp.asarray(r.randn(v, 8), jnp.float32)
    idx = r.randint(0, v, (b, l)).astype(np.int32)
    out1 = ops.embedding_bag(table, jnp.asarray(idx))
    # permutation invariance
    perm = np.stack([r.permutation(row) for row in idx.reshape(b, l)])
    out2 = ops.embedding_bag(table, jnp.asarray(perm))
    _close(out1, out2, 1e-4)
    # linearity: bag(2*table) == 2*bag(table)
    out3 = ops.embedding_bag(2.0 * table, jnp.asarray(idx))
    _close(out3, 2.0 * np.asarray(out1), 1e-4)


@pytest.mark.parametrize("impl", ["ref", "kernel", "kernel_split"])
def test_sparse_lengths_sum_ragged(rng, impl):
    """Paper Fig. 2 semantics with ragged offsets (padded stream), for the
    oracle and the ragged kernel in one call or many."""
    table = jnp.asarray(rng.randn(50, 8), jnp.float32)
    indices = jnp.asarray(rng.randint(0, 50, (12,)), jnp.int32)
    offsets = jnp.asarray([0, 3, 3, 7, 10], jnp.int32)
    if impl == "ref":
        out = ref.sparse_lengths_sum(table, indices, offsets)
    else:
        out = _row_call(embedding_gather.sparse_lengths_sum, table, indices,
                        offsets, max_l=4, split=impl == "kernel_split")
    for b in range(4):
        lo, hi = int(offsets[b]), int(offsets[b + 1])
        want = np.asarray(table)[np.asarray(indices[lo:hi])].sum(0) \
            if hi > lo else np.zeros(8)
        _close(out[b], want, 1e-5)


@pytest.mark.parametrize("v,d,b,l,split", [(19, 8, 5, 4, False),
                                           (19, 8, 5, 4, True),
                                           (300, 32, 40, 6, True),
                                           (40, 128, 6, 3, True)])
def test_sls_grad_table_matches_oracle(rng, v, d, b, l, split):
    """The scatter-add backward, including runs of one destination row
    that a split walk cuts between calls (few rows, many positions)."""
    n = b * l
    idx = jnp.asarray(rng.randint(0, v, (n + 3,)), jnp.int32)
    off = jnp.asarray(np.sort(rng.randint(0, n + 1, b + 1)), jnp.int32)
    off = off.at[0].set(0)
    g = jnp.asarray(rng.randn(b, d), jnp.float32)
    got = _row_call(embedding_gather.sls_grad_table, g, idx, off, n_rows=v,
                    split=split)
    _close(got, ref.sls_grad_table(g, idx, off, v), 1e-5)


def test_embedding_bag_grad_is_scatter_add(rng):
    table = jnp.asarray(rng.randn(64, 8), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 64, (5, 3)), jnp.int32)
    g = jax.grad(lambda t: ops.embedding_bag(t, idx).sum())(table)
    counts = np.zeros(64)
    for i in np.asarray(idx).reshape(-1):
        counts[i] += 1
    _close(np.asarray(g)[:, 0], counts, 1e-5)


# ---------------------------------------------------------------------------
# Feature interaction (dense engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,d", [(4, 6, 32), (9, 27, 16), (64, 6, 32),
                                   (1, 51, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interaction_matches_oracle(rng, b, f, d, dtype):
    x = jnp.asarray(rng.randn(b, f, d), dtype)
    got = feature_interaction.interaction(x, interpret=True)
    want = ref.interaction(x)
    _close(got, want, 1e-4 if dtype == jnp.float32 else 1e-1)


def test_interaction_tril_shape_and_symmetry(rng):
    x = jnp.asarray(rng.randn(3, 6, 8), jnp.float32)
    z = ref.interaction(x)
    # symmetry
    _close(z, np.swapaxes(np.asarray(z), 1, 2), 1e-5)
    tril = ops.interaction_tril(x)
    assert tril.shape == (3, 15)


@settings(deadline=None, max_examples=15)
@given(st.integers(1, 8), st.integers(2, 10), st.integers(1, 16),
       st.integers(0, 2**31 - 1))
def test_interaction_property_diag_is_norm(b, f, d, seed):
    """Property: diagonal of X X^T equals squared row norms."""
    r = np.random.RandomState(seed % (2**32 - 1))
    x = jnp.asarray(r.randn(b, f, d), jnp.float32)
    z = np.asarray(ref.interaction(x))
    norms = (np.asarray(x) ** 2).sum(-1)
    _close(np.diagonal(z, axis1=1, axis2=2), norms, 1e-4)


# ---------------------------------------------------------------------------
# Flash attention (memory-term kernel)
# ---------------------------------------------------------------------------

def _ref_attn(q, k, v, causal, window):
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) \
        * (q.shape[-1] ** -0.5)
    S = q.shape[1]
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


@pytest.mark.parametrize("s,d,causal,window,bq,bk",
                         [(128, 64, True, None, 64, 64),
                          (96, 32, False, None, 32, 32),
                          (128, 64, True, 32, 64, 32),
                          (100, 16, True, None, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(rng, s, d, causal, window, bq, bk,
                                        dtype):
    from repro.kernels.flash_attention import flash_attention
    q = jnp.asarray(rng.randn(2, s, d), dtype)
    k = jnp.asarray(rng.randn(2, s, d), dtype)
    v = jnp.asarray(rng.randn(2, s, d), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                          bk=bk, interpret=True)
    want = _ref_attn(q, k, v, causal, window)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    _close(got, want, tol)


def test_flash_attention_gqa_matches_repeat(rng):
    from repro.kernels.flash_attention import flash_attention_gqa
    q = jnp.asarray(rng.randn(2, 64, 8, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
    got = flash_attention_gqa(q, k, v, interpret=True)
    # reference: repeat kv to full heads, per-head attention
    kk = jnp.repeat(k, 4, axis=2)
    vv = jnp.repeat(v, 4, axis=2)
    for h in range(8):
        want = _ref_attn(q[:, :, h], kk[:, :, h], vv[:, :, h], True, None)
        _close(got[:, :, h], want, 1e-4)


# ---------------------------------------------------------------------------
# Fused segmented dispatch (the one-walk grouped/cached/sharded kernel)
# ---------------------------------------------------------------------------

from repro.kernels import fused_dispatch  # noqa: E402


def _dense_case(rng, v, b, l, null=None):
    """A dense (b, l) id matrix with ragged structure baked in: each bag
    is cut short at a random length, fill slots pointing at `null`."""
    ids = rng.randint(0, v, (b, l))
    if null is not None:
        lens = rng.randint(0, l + 1, b)
        for i in range(b):
            ids[i, lens[i]:] = null
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("v,d,b,l,split", [(100, 32, 4, 1, False),
                                           (257, 16, 8, 6, False),
                                           (64, 128, 3, 9, False),
                                           (1, 1, 2, 3, False),
                                           (50, 1, 5, 4, False),
                                           (1, 48, 4, 2, False),
                                           (257, 16, 8, 6, True),
                                           (300, 160, 7, 3, True)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_segment_sum_matches_oracle(rng, v, d, b, l, split, dtype):
    table = jnp.asarray(rng.randn(v, d), dtype)
    ids = _dense_case(rng, v, b, l, null=v - 1)
    got = _row_call(fused_dispatch.fused_segment_sum, table, ids,
                    split=split)
    want = ref.fused_segment_sum(table, ids)
    _close(got, want, 1e-5 if dtype == jnp.float32 else 5e-2)


@pytest.mark.parametrize("v,k,d,b,l,split", [(120, 9, 8, 4, 5, False),
                                             (64, 1, 16, 3, 3, False),
                                             (256, 33, 32, 6, 7, False),
                                             (256, 33, 32, 6, 7, True),
                                             (200, 140, 128, 5, 2, True)])
def test_fused_cached_segment_sum_matches_oracle(rng, v, k, d, b, l, split):
    arena = jnp.asarray(rng.randn(v, d), jnp.float32)
    hot = jnp.asarray(rng.randn(k + 1, d), jnp.float32)
    slots = _dense_case(rng, k + 1, b, l)
    cold = _dense_case(rng, v, b, l)
    got = _row_call(fused_dispatch.fused_cached_segment_sum, hot, arena,
                    slots, cold, split=split)
    want = ref.fused_cached_segment_sum(hot, arena, slots, cold)
    _close(got, want, 1e-5)


@pytest.mark.parametrize("v,dim,b,l,split", [(90, 32, 6, 5, False),
                                             (90, 31, 6, 5, True),
                                             (40, 300, 3, 2, True)])
def test_fused_int4_segment_sum_matches_oracle(rng, v, dim, b, l, split):
    packed, scales = ref.int4_pack(jnp.asarray(rng.randn(v, dim),
                                               jnp.float32))
    ids = _dense_case(rng, v, b, l)
    got = _row_call(fused_dispatch.fused_int4_segment_sum, packed, scales,
                    ids, dim=dim, split=split)
    _close(got, ref.fused_int4_segment_sum(packed, scales, ids, dim), 1e-5)


def test_fused_ops_pallas_equals_xla_lookup_and_grad(rng):
    """ops.fused_segment_sum / fused_cached_segment_sum agree between the
    Pallas kernel body (interpret) and the XLA reference — outputs AND
    the custom-VJP gradients, including the pinned-to-zero null rows."""
    v, d, b, l, k, null = 90, 16, 6, 5, 12, 89
    table = jnp.asarray(rng.randn(v, d), jnp.float32)
    ids = _dense_case(rng, v, b, l, null=null)
    hot = jnp.asarray(rng.randn(k + 1, d), jnp.float32).at[k].set(0.0)
    slots = _dense_case(rng, k + 1, b, l, null=k)
    cold = _dense_case(rng, v, b, l, null=null)
    outs, grads = [], []
    for impl in ("xla", "interpret"):
        ops.set_impl(impl)
        try:
            f = lambda t: ops.fused_segment_sum(t, ids, null_row=null)
            outs.append(np.asarray(f(table)))
            g = jax.grad(lambda t: f(t).sum())(table)
            fc = lambda h, a: ops.fused_cached_segment_sum(
                h, a, slots, cold, null_row=null)
            outs.append(np.asarray(fc(hot, table)))
            gh, ga = jax.grad(lambda h, a: fc(h, a).sum(),
                              argnums=(0, 1))(hot, table)
            grads.append((np.asarray(g), np.asarray(gh), np.asarray(ga)))
        finally:
            ops.set_impl("auto")
    _close(outs[0], outs[2], 1e-5)
    _close(outs[1], outs[3], 1e-5)
    for a, bb in zip(grads[0], grads[1]):
        _close(a, bb, 1e-5)
    # the sentinel rows never receive gradient (the ragged tail-mask law)
    g, gh, ga = grads[0]
    assert (g[null] == 0).all() and (gh[k] == 0).all() \
        and (ga[null] == 0).all()


def test_fused_degenerate_bags(rng):
    """Degenerate shapes the relayout must survive: empty bags,
    all-duplicate bags, all-null bags, vocab-1/dim-1 tables, max_l=0."""
    d = 8
    table = jnp.asarray(rng.randn(40, d), jnp.float32).at[39].set(0.0)
    null = 39
    # empty bags: every slot is fill -> exact zeros
    empty = jnp.full((3, 4), null, jnp.int32)
    assert (np.asarray(ops.fused_segment_sum(table, empty)) == 0).all()
    # all-duplicate bag: L * row, bit-for-bit against the closed form
    dup = jnp.full((1, 6), 7, jnp.int32)
    got = np.asarray(ops.fused_segment_sum(table, dup))
    want = np.asarray(table[7], np.float32)[None, :] * 6.0
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # max_l == 0: (B, 0) ids -> zeros, on both backends
    zero_ids = jnp.zeros((4, 0), jnp.int32)
    assert ops.fused_segment_sum(table, zero_ids).shape == (4, d)
    assert (np.asarray(
        fused_dispatch.fused_segment_sum(table, zero_ids,
                                         interpret=True)) == 0).all()
    # vocab-1 / dim-1
    t1 = jnp.asarray(rng.randn(1, 1), jnp.float32)
    ids1 = jnp.zeros((2, 3), jnp.int32)
    got1 = np.asarray(ops.fused_segment_sum(t1, ids1))
    np.testing.assert_allclose(got1, np.full((2, 1), 3 * float(t1[0, 0]),
                                             np.float32), rtol=1e-6)
    # all-null bags still take zero gradient on the sentinel
    g = jax.grad(lambda t: ops.fused_segment_sum(
        t, empty, null_row=null).sum())(table)
    assert (np.asarray(g) == 0).all()


def test_fused_cached_one_pass_equals_uncached_bitwise(rng):
    """The in-kernel hit-test law: splitting any dense id matrix into
    (hot slots, cold redirects) and running the one-pass cached reduce is
    BIT-FOR-BIT the uncached reduce, and the hot/cold gradients recombine
    to exactly the uncached gradient."""
    v, d, b, l, k, null = 80, 8, 5, 6, 10, 79
    table = jnp.asarray(rng.randn(v, d), jnp.float32).at[null].set(0.0)
    ids = _dense_case(rng, v, b, l, null=null)
    # hot set: the k most frequent ids (never the sentinel, matching
    # build_hot_cache); hot_rows copies arena rows
    counts = np.bincount(np.asarray(ids).ravel(), minlength=v)
    counts[null] = -1
    hot_ids = np.argsort(counts)[-k:]
    slot_of = np.full(v, k, np.int32)
    slot_of[hot_ids] = np.arange(k)
    slot_of = jnp.asarray(slot_of)
    hot_rows = jnp.concatenate([table[jnp.asarray(hot_ids)],
                                jnp.zeros((1, d), jnp.float32)])
    slots = jnp.take(slot_of, ids)
    cold = jnp.where(slots < k, jnp.asarray(null, ids.dtype), ids)
    got = np.asarray(ops.fused_cached_segment_sum(hot_rows, table, slots,
                                                  cold, null_row=null))
    want = np.asarray(ops.fused_segment_sum(table, ids, null_row=null))
    np.testing.assert_array_equal(got, want)
    # gradient law: scatter d_hot back onto its arena rows + d_arena
    # == the uncached arena gradient, exactly
    g_un = jax.grad(lambda t: ops.fused_segment_sum(
        t, ids, null_row=null).sum())(table)
    gh, ga = jax.grad(
        lambda h, a: ops.fused_cached_segment_sum(
            h, a, slots, cold, null_row=null).sum(),
        argnums=(0, 1))(hot_rows, table)
    recomb = np.array(ga)
    recomb[hot_ids] += np.asarray(gh)[:k]
    np.testing.assert_array_equal(recomb, np.asarray(g_un))


def test_fused_cached_coherent_lowering_same_value_same_split(rng):
    """Passing dense_ids= opts into the coherence-law lowering: the
    forward equals both the uncached reduce (bitwise, on xla) and the
    two-table walk (which it replaces on xla but not on the kernel
    path), while the gradients still split onto hot slots / cold ids
    exactly as the explicit two-pass op's do."""
    v, d, b, l, k, null = 70, 8, 5, 6, 9, 69
    table = jnp.asarray(rng.randn(v, d), jnp.float32).at[null].set(0.0)
    ids = _dense_case(rng, v, b, l, null=null)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=v)
    counts[null] = -1
    hot_ids = np.argsort(counts)[-k:]
    slot_of = np.full(v, k, np.int32)
    slot_of[hot_ids] = np.arange(k)
    slots = jnp.take(jnp.asarray(slot_of), ids)
    cold = jnp.where(slots < k, jnp.asarray(null, ids.dtype), ids)
    hot_rows = jnp.concatenate([table[jnp.asarray(hot_ids)],
                                jnp.zeros((1, d), jnp.float32)])
    for impl in ("xla", "interpret"):
        ops.set_impl(impl)
        try:
            coh = lambda h, a: ops.fused_cached_segment_sum(
                h, a, slots, cold, dense_ids=ids, null_row=null)
            split = lambda h, a: ops.fused_cached_segment_sum(
                h, a, slots, cold, null_row=null)
            got = np.asarray(coh(hot_rows, table))
            np.testing.assert_allclose(
                got, np.asarray(split(hot_rows, table)), rtol=1e-5,
                atol=1e-6)
            if impl == "xla":
                np.testing.assert_array_equal(
                    got, np.asarray(ops.fused_segment_sum(
                        table, ids, null_row=null)))
            g_coh = jax.grad(lambda h, a: coh(h, a).sum(),
                             argnums=(0, 1))(hot_rows, table)
            g_split = jax.grad(lambda h, a: split(h, a).sum(),
                               argnums=(0, 1))(hot_rows, table)
            for a, bb in zip(g_coh, g_split):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(bb))
            assert np.abs(np.asarray(g_coh[0])[:-1]).max() > 0
        finally:
            ops.set_impl("auto")


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_fused_sharded_partial_equals_replicated(rng, shards):
    """The sharded law over the dense id matrix: every shard's masked
    partial reduce psums back to the replicated fused reduce (vmap-
    emulated mesh), for shard counts {1, 2, 4, 8}."""
    from repro.core import sparse_engine as se
    v, d, b, l = 8 * 13, 16, 6, 5
    null = v - 1
    table = jnp.asarray(rng.randn(v, d), jnp.float32).at[null].set(0.0)
    ids = _dense_case(rng, v, b, l, null=null)
    want = np.asarray(ops.fused_segment_sum(table, ids, null_row=null))
    outs = jax.vmap(
        lambda a: se.dense_partial_reduce(a, ids, "x", null_row=null),
        axis_name="x")(table.reshape(shards, -1, d))
    for s in range(shards):
        np.testing.assert_allclose(np.asarray(outs[s]), want, rtol=1e-5,
                                   atol=1e-5)
