"""Property suite for repro.storage — tiered, bigger-than-memory serving.

The laws this file pins:

* **composition** — a ``TieredSource`` lookup is hot + warm + cold with
  hot rows bit-exact vs the fp arena, warm/cold within their per-row
  quantization bounds, and host-staged cold rows exact fp32 copies;
  gradients flow to the hot tier through the same fused VJP.
* **grouped == per-table** — a ``TableGroupSource`` with a tiered member
  still equals the per-table loop of its members' own lookups.
* **migration** — ``migrate`` with a correct dirty mask is bit-identical
  to a full ``build_tiered`` rebuild, and republishing the migrated
  source under a bumped version never recompiles the serve path.
* **staging residency** — ``HostStore.stage`` guarantees residency for
  the in-flight batch (hits + misses == touches), never evicts pinned
  rows for lookahead, truncates best-effort prefetch before the
  guarantee, and raises (then recovers) when a batch exceeds the arena.
* **artifacts** — the checkpoint manager round-trips ``VersionedSource``
  blobs (tiered and grouped included; a host tier's live store is
  ephemeral and comes back ``None`` still serving its staged snapshot).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import storage
from repro.checkpoint import CheckpointManager
from repro.configs.base import DLRMConfig
from repro.core import dlrm
from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.kernels import ops

CFG = DLRMConfig(name="dlrm_storage", n_tables=2, rows_per_table=200,
                 emb_dim=8, lookups_per_table=4,
                 bottom_mlp=(16, 8), top_mlp=(16, 1))


def _arena(spec, seed=0, scale=1.0):
    return se.init_arena(jax.random.PRNGKey(seed), spec, scale=scale)


def _ragged(rng, spec, n_bags, max_l):
    lens = rng.randint(0, max_l + 1, n_bags).astype(np.int32)
    off = np.zeros(n_bags + 1, np.int32)
    np.cumsum(lens, out=off[1:])
    idx = rng.randint(0, spec.total_rows - 1, off[-1]).astype(np.int32)
    return jnp.asarray(idx), jnp.asarray(off)


def _policy(cold, spec, hot=20, warm=80, staging_rows=64, max_stage=32):
    return storage.TierPolicy(hot=hot, warm=warm, cold=cold,
                              staging_rows=staging_rows,
                              max_stage_per_batch=max_stage)


def _stage_all(tiered, idx):
    """Guarantee residency for every cold row `idx` touches, then
    snapshot the refreshed tier (what RecEngine does per batch)."""
    for st in storage.host_stores_of(tiered):
        st.stage_arena(np.asarray(idx))
    return storage.refresh_host_tiers(tiered)


# ---------------------------------------------------------------------------
# int4 pack/unpack and quantize_rows (the representation primitives)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim", [1, 7, 8])
def test_int4_round_trip_within_bound(dim):
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(40, dim).astype(np.float32))
    packed, scales = ops.int4_pack(a)
    assert packed.shape == (40, (dim + 1) // 2) and packed.dtype == jnp.uint8
    back = ops.int4_unpack(packed, scales, dim)
    # symmetric round-to-nearest at 4 bits: |err| <= scale/2 = amax/14
    bound = np.asarray(jnp.abs(a).max(axis=1)) / 14.0 + 1e-6
    err = np.abs(np.asarray(back) - np.asarray(a)).max(axis=1)
    assert (err <= bound).all(), (err, bound)


def test_int4_zero_row_is_exact_and_inert():
    a = jnp.zeros((3, 6), jnp.float32)
    packed, scales = ops.int4_pack(a)
    assert float(jnp.abs(scales).max()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(ops.int4_unpack(packed, scales, 6)), np.zeros((3, 6)))


def test_quantize_rows_degenerate_inputs():
    """Empty row sets, duplicate ids, and all-zero rows: the incremental
    patch stays bit-identical to a full rebuild."""
    rng = np.random.RandomState(7)
    arena = jnp.asarray(rng.randn(30, 5).astype(np.float32))
    arena = arena.at[4].set(0.0)                    # an all-zero row
    full = es.QuantizedArena.from_arena(arena)

    # empty patch: a no-op
    same = full.quantize_rows(arena, jnp.zeros(0, jnp.int32))
    np.testing.assert_array_equal(np.asarray(same.q), np.asarray(full.q))

    # duplicate ids are an idempotent set; zero row keeps its zero scale
    stale = es.QuantizedArena(q=jnp.zeros_like(full.q),
                              scales=jnp.zeros_like(full.scales))
    rows = jnp.asarray([4, 9, 9, 4, 12], jnp.int32)
    patched = stale.quantize_rows(arena, rows)
    for r in (4, 9, 12):
        np.testing.assert_array_equal(np.asarray(patched.q[r]),
                                      np.asarray(full.q[r]))
    assert float(patched.scales[4, 0]) == 0.0
    assert float(jnp.abs(patched.q[0]).max()) == 0.0   # untouched row


# ---------------------------------------------------------------------------
# the composition law: hot bit-exact, warm/cold bounded, grads flow
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cold", ["int4", "host"])
def test_tiered_lookup_composition(cold):
    spec = se.ArenaSpec(1, 150, 8)
    arena = _arena(spec, seed=1)
    rng = np.random.RandomState(11)
    counts = rng.rand(spec.total_rows)
    pol = _policy(cold, spec, hot=15, warm=60)
    tiered = storage.build_tiered(arena, spec, pol, counts)
    idx, off = _ragged(rng, spec, n_bags=12, max_l=5)
    tiered = _stage_all(tiered, idx)

    got = np.asarray(es.lookup_bags(tiered, spec, idx, off, max_l=5))
    want = np.asarray(es.lookup_bags(es.FpArena(arena), spec, idx, off,
                                     max_l=5))
    # per-bag bound: each warm row errs <= amax/254, each int4 cold row
    # <= amax/14, host-staged rows are exact — sum over <= max_l rows
    amax = float(jnp.abs(arena).max())
    per_row = amax / 254.0 + (amax / 14.0 if cold == "int4" else 0.0)
    assert np.abs(got - want).max() <= 5 * per_row + 1e-5

    # hot rows alone: bit-exact (bags touching only hot arena ids)
    hot_ids = np.asarray(tiered.hot_ids)
    hidx = jnp.asarray(hot_ids[:10], jnp.int32)
    hoff = jnp.asarray(np.arange(0, 11, 1, np.int32))
    np.testing.assert_array_equal(
        np.asarray(es.lookup_bags(tiered, spec, hidx, hoff, max_l=5)),
        np.asarray(es.lookup_bags(es.FpArena(arena), spec, hidx, hoff,
                                  max_l=5)))


def test_host_staged_rows_serve_exact_fp32():
    """A cold row served through the staging arena equals the fp arena
    row exactly — the composition law extends to the host tier."""
    spec = se.ArenaSpec(1, 100, 4)
    arena = _arena(spec, seed=2)
    pol = _policy("host", spec, hot=5, warm=10, staging_rows=32)
    tiered = storage.build_tiered(arena, spec, pol,
                                  np.arange(spec.total_rows)[::-1])
    cold_arena_ids = np.nonzero(
        np.asarray(tiered.tier_slot) >= tiered.n_hot + tiered.n_warm)[0]
    cold_arena_ids = cold_arena_ids[cold_arena_ids != spec.null_row][:16]
    idx = jnp.asarray(cold_arena_ids, jnp.int32)
    off = jnp.asarray(np.arange(len(cold_arena_ids) + 1, dtype=np.int32))
    tiered = _stage_all(tiered, idx)
    np.testing.assert_array_equal(
        np.asarray(es.lookup_bags(tiered, spec, idx, off, max_l=4)),
        np.asarray(es.lookup_bags(es.FpArena(arena), spec, idx, off,
                                  max_l=4)))


def test_tiered_grads_flow_to_hot_tier():
    """d(lookup)/d(hot_rows) through the fused VJP: nonzero exactly on
    the touched hot slots, zero on untouched slots and the null slot."""
    spec = se.ArenaSpec(1, 80, 6)
    arena = _arena(spec, seed=3)
    pol = _policy("int4", spec, hot=10, warm=30)
    tiered = storage.build_tiered(arena, spec, pol,
                                  np.arange(spec.total_rows)[::-1])
    hot_ids = np.asarray(tiered.hot_ids)
    idx = jnp.asarray(hot_ids[:4], jnp.int32)      # touch 4 hot rows
    off = jnp.asarray([0, 2, 4], jnp.int32)

    def loss(hot_rows):
        src = dataclasses.replace(tiered, hot_rows=hot_rows)
        return es.lookup_bags(src, spec, idx, off, max_l=4).sum()

    g = np.asarray(jax.grad(loss)(tiered.hot_rows))
    assert (np.abs(g[:4]).sum(axis=1) > 0).all()   # touched slots
    assert np.abs(g[4:]).max() == 0.0              # untouched + null


def test_grouped_equals_per_table_with_tiered_member():
    """A group mixing a tiered member (host cold) with a plain fp member
    still satisfies grouped == per-table, bit for bit."""
    vocabs, dims = (60, 40), (8, 4)
    plans = (es.TablePlan(rows=60, dim=8,
                          tiers=_policy("host", None, hot=6, warm=20,
                                        staging_rows=40)),
             es.TablePlan(rows=40, dim=4))
    specs = tuple(tp.arena_spec for tp in plans)
    arenas = [_arena(sp, seed=10 + t) for t, sp in enumerate(specs)]
    group = es.SourceSpec(tables=plans).build(arenas, None)
    assert isinstance(group.members[0], storage.TieredSource)

    rng = np.random.RandomState(5)
    b, max_l, t_count = 6, 4, 2
    lens = rng.randint(0, max_l + 1, b * t_count).astype(np.int32)
    off = np.zeros(b * t_count + 1, np.int32)
    np.cumsum(lens, out=off[1:])
    idx = np.concatenate([
        rng.randint(0, vocabs[i % t_count], lens[i]).astype(np.int32)
        for i in range(b * t_count)]) if off[-1] else np.zeros(0, np.int32)

    # stage the tiered member's cold rows for table 0's stream
    idx_t, off_t = [], []
    for t in range(t_count):
        bags = [idx[off[i]:off[i + 1]]
                for i in range(t, b * t_count, t_count)]
        idx_t.append(jnp.asarray(np.concatenate(bags)
                                 if bags else np.zeros(0, np.int32)))
        off_t.append(jnp.asarray(np.cumsum(
            [0] + [len(x) for x in bags]).astype(np.int32)))
    for st in storage.host_stores_of(group):
        st.stage_arena(np.asarray(idx_t[0]))
    group = storage.refresh_host_tiers(group)

    got = np.asarray(es.lookup_bags(group, group.envelope_spec,
                                    jnp.asarray(idx), jnp.asarray(off),
                                    max_l=max_l))
    for t, (m, sp) in enumerate(zip(group.members, group.specs)):
        own = np.asarray(es.lookup_bags(m, sp, idx_t[t], off_t[t],
                                        max_l=max_l))[:, 0, :]
        np.testing.assert_array_equal(got[:, t, :sp.dim],
                                      own.astype(got.dtype))
        assert (got[:, t, sp.dim:] == 0).all()


# ---------------------------------------------------------------------------
# migration: incremental == full rebuild; structure stability
# ---------------------------------------------------------------------------

def test_migrate_incremental_equals_full_rebuild():
    spec = se.ArenaSpec(1, 120, 6)
    arena0 = _arena(spec, seed=4)
    rng = np.random.RandomState(9)
    pol = _policy("int4", spec, hot=12, warm=50)
    t0 = storage.build_tiered(arena0, spec, pol, rng.rand(spec.total_rows))

    # train-like drift: some rows change values (dirty), ranks reshuffle
    touched = rng.choice(spec.total_rows - 1, 20, replace=False)
    arena1 = arena0.at[jnp.asarray(touched)].add(0.5)
    dirty = np.zeros(spec.total_rows, bool)
    dirty[touched] = True
    counts1 = rng.rand(spec.total_rows)

    mig, stats = storage.migrate(t0, arena1, spec, pol, counts1, dirty)
    full = storage.build_tiered(arena1, spec, pol, counts1)
    for f in ("hot_rows", "tier_slot", "hot_ids"):
        np.testing.assert_array_equal(np.asarray(getattr(mig, f)),
                                      np.asarray(getattr(full, f)), f)
    np.testing.assert_array_equal(np.asarray(mig.warm.q),
                                  np.asarray(full.warm.q))
    np.testing.assert_array_equal(np.asarray(mig.warm.scales),
                                  np.asarray(full.warm.scales))
    np.testing.assert_array_equal(np.asarray(mig.cold.packed),
                                  np.asarray(full.cold.packed))
    np.testing.assert_array_equal(np.asarray(mig.cold.scales),
                                  np.asarray(full.cold.scales))
    assert stats["promoted_hot"] == stats["demoted_hot"]   # fixed H
    assert stats["warm_requant"] <= spec.total_rows


def test_migrate_host_cold_retargets_in_place():
    """A host cold tier migrates by retargeting the SAME store object
    (treedef stability) and resets residency."""
    spec = se.ArenaSpec(1, 90, 4)
    arena = _arena(spec, seed=6)
    pol = _policy("host", spec, hot=8, warm=20, staging_rows=64)
    rng = np.random.RandomState(2)
    t0 = storage.build_tiered(arena, spec, pol, rng.rand(spec.total_rows))
    store = t0.cold.store
    store.stage_arena(np.arange(50))
    assert store.stats()["resident"] > 0
    mig, _ = storage.migrate(t0, arena, spec, pol,
                             rng.rand(spec.total_rows))
    assert mig.cold.store is store                 # same identity
    assert store.stats()["resident"] == 0          # residency reset
    assert (jax.tree_util.tree_structure(mig)
            == jax.tree_util.tree_structure(t0))


# ---------------------------------------------------------------------------
# HostStore residency semantics
# ---------------------------------------------------------------------------

def _store(c=40, d=4, s=16, max_stage=8):
    rows = np.arange(c * d, dtype=np.float32).reshape(c, d) + 1.0
    return storage.HostStore(rows, staging_rows=s,
                             max_stage_per_batch=max_stage), rows


def test_stage_accounting_and_bit_exact_rows():
    st, rows = _store()
    hits, misses = st.stage(np.array([3, 7, 7, 11]))
    assert (hits, misses) == (0, 3)                # unique ids
    hits, misses = st.stage(np.array([3, 7, 11, 20]))
    assert (hits, misses) == (3, 1)
    assert st.touches == st.hits + st.misses == 7
    tier = st.tier()
    slot = np.asarray(tier.slot_of)
    for i in (3, 7, 11, 20):
        np.testing.assert_array_equal(np.asarray(tier.staging[slot[i]]),
                                      rows[i])
    # non-resident ids point at the zero null slot
    assert slot[30] == st.staging_rows
    assert float(jnp.abs(tier.staging[-1]).max()) == 0.0


def test_stage_with_ahead_merges_one_plan():
    """Lookahead rides the same flush uncounted, then arrives as hits;
    need∩ahead overlap never double-assigns a slot."""
    st, _ = _store(s=16)
    cur, nxt = np.array([0, 1, 2]), np.array([2, 3, 4])   # overlap on 2
    hits, misses = st.stage(cur, ahead=nxt)
    assert (hits, misses) == (0, 3)                # only cur counted
    hits, misses = st.stage(nxt)
    assert (hits, misses) == (3, 0)                # lookahead landed
    # owner/slot maps agree: every resident id owns exactly one slot
    res = np.nonzero(st._slot_np[:-1] < st.staging_rows)[0]
    slots = st._slot_np[res]
    assert len(np.unique(slots)) == len(res)
    np.testing.assert_array_equal(st._owner[slots], res)


def test_pinned_rows_never_evicted_by_prefetch():
    st, _ = _store(c=40, s=8)
    st.stage(np.arange(8))                         # pin the full arena
    assert st.prefetch(np.arange(8, 20)) == 0      # nothing evictable
    assert (st._slot_np[np.arange(8)] < st.staging_rows).all()
    # next batch unpins: now the prefetch can evict LRU rows
    st.stage(np.array([0, 1]))
    assert st.prefetch(np.arange(8, 12)) == 4
    assert (st._slot_np[[0, 1]] < st.staging_rows).all()   # still pinned


def test_staging_too_small_raises_then_recovers():
    st, rows = _store(c=40, s=8)
    with pytest.raises(ValueError, match="staging arena too small"):
        st.stage(np.arange(12))                    # 12 > 8 slots
    hits, misses = st.stage(np.array([1, 2]))      # still functional
    assert misses == 2
    tier = st.tier()
    np.testing.assert_array_equal(
        np.asarray(tier.staging[np.asarray(tier.slot_of)[1]]), rows[1])


def test_lru_eviction_prefers_oldest_unpinned():
    st, _ = _store(c=40, s=8, max_stage=8)
    st.stage(np.arange(0, 4))                      # oldest
    st.stage(np.arange(4, 8))                      # arena now full
    st.stage(np.arange(8, 11))                     # must evict 3 of 0..3
    assert (st._slot_np[8:11] < st.staging_rows).all()
    assert (st._slot_np[4:8] < st.staging_rows).all()      # pinned batch
    evicted = (st._slot_np[0:4] == st.staging_rows).sum()
    assert evicted == 3


def test_warm_compile_does_not_disturb_residency():
    st, rows = _store()
    st.stage(np.array([5, 6]))
    before = np.asarray(st.tier().slot_of).copy()
    st.warm_compile()
    np.testing.assert_array_equal(np.asarray(st.tier().slot_of), before)
    tier = st.tier()
    np.testing.assert_array_equal(np.asarray(tier.staging[before[5]]),
                                  rows[5])


def test_store_structural_equality_for_jit_signatures():
    a, _ = _store(c=40, s=16)
    b, _ = _store(c=40, s=16)
    c, _ = _store(c=40, s=8)
    assert a == b and hash(a) == hash(b)           # interchangeable
    assert a != c


# ---------------------------------------------------------------------------
# engine: tiered serving, zero recompiles across version bumps
# ---------------------------------------------------------------------------

def test_engine_serves_tiered_with_zero_recompiles_across_migrations():
    from repro.serving import RecEngine
    from repro.serving.rec_engine import requests_from_ragged_batch
    from repro.training import make_drifting_zipf

    cfg = CFG
    spec = dlrm.arena_spec(cfg)
    params = dlrm.init(jax.random.PRNGKey(0), cfg)
    pol = storage.TierPolicy(hot=20, warm=150, cold="host",
                             staging_rows=128, max_stage_per_batch=32)
    eng = RecEngine(cfg, params, source=es.SourceSpec(tiers=pol),
                    max_l=6, max_batch=8, max_wait_ms=0.0, buckets=(8,))
    eng.warmup()
    compiled = eng._serve._cache_size()
    gen = make_drifting_zipf(cfg, batch_size=8, mean_l=3, max_l=6,
                             drift_per_batch=2, alpha=1.3, seed=1)

    def drive(n):
        for _ in range(n):
            for r in requests_from_ragged_batch(next(gen), cfg.n_tables):
                eng.submit(r)
            eng.step(force=True)
        eng.drain()

    drive(4)
    assert eng.stats()["path"] == "tiered"
    store = eng._host_stores[0][0]
    s = store.stats()
    assert s["hits"] + s["misses"] == s["touches"]

    # three migration republishes under bumped versions: same executable
    for _ in range(3):
        hist = np.zeros(spec.total_rows)
        b = next(gen)
        hist += se.trace_row_counts(spec, b["indices"], b["offsets"])
        migrated, _ = storage.migrate(eng.source, params["arena"], spec,
                                      pol, hist)
        eng.update_source(migrated, version=eng.source_version + 1)
        drive(2)
    assert eng._serve._cache_size() == compiled, \
        "tier migration republish recompiled the serve path"
    s = store.stats()
    assert s["hits"] + s["misses"] == s["touches"]


# ---------------------------------------------------------------------------
# trainer maintenance: tiered hot tier stays write-through fresh
# ---------------------------------------------------------------------------

def test_online_trainer_maintains_tiered_source():
    from repro.training import (OnlineCacheConfig, OnlineTrainer,
                                make_drifting_zipf)

    cfg = CFG
    params = dlrm.init(jax.random.PRNGKey(1), cfg)
    pol = storage.TierPolicy(hot=16, warm=100, cold="int4")
    trainer = OnlineTrainer(cfg, params, max_l=6, lr=1e-2,
                            cache_cfg=OnlineCacheConfig(
                                k=0, refresh_every=5, tiers=pol))
    assert isinstance(trainer.tiered, storage.TieredSource)
    gen = make_drifting_zipf(cfg, batch_size=8, mean_l=3, max_l=6,
                             drift_per_batch=2, alpha=1.2, seed=3)
    for _ in range(12):
        trainer.train_step(next(gen))
    assert trainer.version >= 2                    # migrations happened
    # write-through law: the fp hot tier equals the live arena bit-exact
    hot = np.asarray(trainer.tiered.hot_rows[:-1])
    want = np.asarray(jnp.take(trainer.params["arena"],
                               trainer.tiered.hot_ids, axis=0))
    np.testing.assert_array_equal(hot, want)
    assert trainer.serving_source() is trainer.tiered
    blob = trainer.publish_source()
    v = es.VersionedSource.deserialize(blob)
    assert v.version == trainer.version
    assert isinstance(v.source, storage.TieredSource)


def test_observe_is_a_noop_without_cache_cfg(monkeypatch):
    """No histogram consumer, no histogram cost: observe must early-return
    before touching the trace-count path."""
    from repro.training import OnlineTrainer
    from repro.training import online as online_mod

    cfg = CFG
    params = dlrm.init(jax.random.PRNGKey(2), cfg)
    trainer = OnlineTrainer(cfg, params, max_l=6, lr=1e-2)

    def boom(*a, **k):
        raise AssertionError("observe touched trace_row_counts "
                             "without a cache_cfg")

    monkeypatch.setattr(online_mod.se, "trace_row_counts", boom)
    trainer.observe({"indices": np.zeros(4, np.int32),
                     "offsets": np.zeros(5, np.int32)})


# ---------------------------------------------------------------------------
# artifacts: describe, tier_bytes, serializer + checkpoint round trips
# ---------------------------------------------------------------------------

def test_describe_source_reports_tiers_and_bytes():
    spec = se.ArenaSpec(1, 100, 8)
    arena = _arena(spec, seed=8)
    for cold, label in (("int4", "tiered(int4)"), ("host", "tiered(host)")):
        t = storage.build_tiered(arena, spec,
                                 _policy(cold, spec, hot=10, warm=40),
                                 np.arange(spec.total_rows))
        assert es.describe_source(t) == label
        ml = es.describe_source(t, multiline=True)
        assert "hot  fp" in ml and "warm int8" in ml
        assert ("int4 arena" in ml) == (cold == "int4")
        assert ("host tier" in ml) == (cold == "host")
        assert " B" in ml or " KB" in ml           # byte sizes rendered


def test_tier_bytes_accounting_sums():
    spec = se.ArenaSpec(1, 100, 8)
    arena = _arena(spec, seed=8)
    t = storage.build_tiered(arena, spec,
                             _policy("host", spec, hot=10, warm=40,
                                     staging_rows=16),
                             np.arange(spec.total_rows))
    b = storage.tier_bytes(t)
    assert b["device_total"] == b["hot"] + b["warm"] + b["cold"] + b["maps"]
    assert b["host"] == t.n_cold * spec.dim * 4    # fp32 host block
    assert b["cold"] == (16 + 1) * spec.dim * 4 + (t.n_cold + 1) * 4


@pytest.mark.parametrize("cold", ["int4", "host"])
def test_versioned_source_round_trips_tiered(cold):
    spec = se.ArenaSpec(1, 80, 4)
    arena = _arena(spec, seed=9)
    rng = np.random.RandomState(4)
    t = storage.build_tiered(arena, spec,
                             _policy(cold, spec, hot=8, warm=30,
                                     staging_rows=32),
                             rng.rand(spec.total_rows))
    idx, off = _ragged(rng, spec, n_bags=10, max_l=4)
    t = _stage_all(t, idx)
    blob = es.VersionedSource(source=t, version=7).serialize()
    v = es.VersionedSource.deserialize(blob)
    assert v.version == 7
    if cold == "host":
        assert v.source.cold.store is None         # ephemeral dropped
    np.testing.assert_array_equal(
        np.asarray(es.lookup_bags(v.source, spec, idx, off, max_l=4)),
        np.asarray(es.lookup_bags(t, spec, idx, off, max_l=4)))


def test_checkpoint_manager_round_trips_sources(tmp_path):
    """save_source/restore_source: tmp-then-rename publish, keep-N GC in
    its own src_* namespace, and a grouped source with a tiered member
    (host cold) restores to a blob that serves its staged snapshot."""
    plans = (es.TablePlan(rows=60, dim=8,
                          tiers=_policy("host", None, hot=6, warm=20,
                                        staging_rows=40)),
             es.TablePlan(rows=40, dim=4))
    specs = tuple(tp.arena_spec for tp in plans)
    arenas = [_arena(sp, seed=20 + t) for t, sp in enumerate(specs)]
    group = es.SourceSpec(tables=plans).build(arenas, None)
    for st in storage.host_stores_of(group):
        st.stage_arena(np.arange(60))
    group = storage.refresh_host_tiers(group)

    mgr = CheckpointManager(tmp_path, keep_n=2)
    for step, src in ((1, es.FpArena(arenas[1])), (2, group),
                      (3, group)):
        mgr.save_source(step, es.VersionedSource(source=src,
                                                 version=step))
    assert mgr.source_steps() == [2, 3]            # keep-N applied
    assert mgr.latest_source_step() == 3
    mgr.save(4, {"w": arenas[1]})                  # param namespace
    assert mgr.source_steps() == [2, 3]            # unaffected

    restored, manifest = mgr.restore_source()
    assert manifest["step"] == 3 and restored.version == 3
    assert isinstance(restored.source, es.TableGroupSource)
    assert restored.source.members[0].cold.store is None
    rng = np.random.RandomState(6)
    idx = jnp.asarray(rng.randint(0, 40, 12).astype(np.int32))
    off = jnp.asarray(np.linspace(0, 12, 7).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(es.lookup_bags(restored.source,
                                  restored.source.envelope_spec,
                                  idx, off, max_l=4)),
        np.asarray(es.lookup_bags(group, group.envelope_spec,
                                  idx, off, max_l=4)))
    with pytest.raises(FileNotFoundError):
        CheckpointManager(tmp_path / "empty").restore_source()


def test_plan_validation_rejects_conflicting_knobs():
    pol = _policy("int4", None)
    with pytest.raises(ValueError, match="caching/quantization"):
        es.TablePlan(rows=10, dim=4, cache_k=5, tiers=pol)
    with pytest.raises(ValueError):
        es.SourceSpec(cache_k=8, tiers=pol)
    with pytest.raises(ValueError):
        es.SourceSpec(layout="fixed", tiers=pol)
    with pytest.raises(AssertionError):
        storage.TierPolicy(hot=4, warm=4, cold="float8")


def test_import_starts_no_backend():
    """Importing the storage tiers (and with them the serving and training
    packages) must not start a JAX backend: on a TPU host that would take
    the chip in any process that merely imports them."""
    import os
    import subprocess
    import sys
    code = ("import repro.storage, repro.serving, repro.training\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": src,
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
