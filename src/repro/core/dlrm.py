"""DLRM — the paper's model (Fig. 1/3), built on the sparse + dense engines.

Topology: dense features -> bottom MLP ─┐
          sparse indices -> embedding    ├─> feature interaction -> top MLP
          gather+reduce (sparse engine) ─┘         -> sigmoid -> CTR

Training uses row-wise Adagrad on the embedding arena (sparse engine state)
and AdamW on the MLPs, matching production DLRM practice.
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import DLRMConfig
from repro.core import dense_engine as de
from repro.core import embedding_source as es
from repro.core import sparse_engine as se
from repro.obs.tracing import stage as obs_stage
from repro.optim import Optimizer, adamw, partitioned, rowwise_adagrad


def arena_spec(cfg: DLRMConfig) -> se.ArenaSpec:
    """The uniform ArenaSpec, or — for a heterogeneous config — the
    group's *envelope* (n_tables, max vocab, max dim): the entry points
    consume only its n_tables/dim fields for a table group (a group never
    flattens into one shared arena)."""
    if cfg.heterogeneous:
        return se.ArenaSpec(cfg.n_tables, max(cfg.table_rows),
                            max(cfg.table_dims), cfg.dtype)
    return se.ArenaSpec(cfg.n_tables, cfg.rows_per_table, cfg.emb_dim,
                        cfg.dtype)


def member_specs(cfg: DLRMConfig):
    """Per-table single-table ArenaSpecs of a heterogeneous config."""
    return tuple(se.ArenaSpec(1, r, d, cfg.dtype)
                 for r, d in zip(cfg.resolved_table_rows,
                                 cfg.resolved_table_dims))


def table_plans(cfg: DLRMConfig, *, cache_k=0,
                quantize_rows_above: Optional[int] = None):
    """The declarative per-table composition for a heterogeneous config:
    ``cache_k`` (int or per-table sequence; 0 = no hot cache for that
    table) pins the skewed tables, ``quantize_rows_above`` int8-quantizes
    every table whose vocab exceeds the threshold (the huge tables whose
    fp32 rows blow the capacity budget). Returns the TablePlan tuple a
    ``SourceSpec(tables=...)`` consumes."""
    rows = cfg.resolved_table_rows
    dims = cfg.resolved_table_dims
    if not isinstance(cache_k, (tuple, list)):
        cache_k = (cache_k,) * cfg.n_tables
    return tuple(es.TablePlan(
        rows=r, dim=d, cache_k=int(k),
        quantize=(quantize_rows_above is not None
                  and r > quantize_rows_above))
        for r, d, k in zip(rows, dims, cache_k))


def top_mlp_in_dim(cfg: DLRMConfig) -> int:
    f = cfg.n_interact_features
    return cfg.emb_dim + f * (f - 1) // 2


def init(key: jax.Array, cfg: DLRMConfig, shards: int = 1) -> Dict:
    k_arena, k_bot, k_top = jax.random.split(key, 3)
    assert cfg.bottom_mlp[-1] == cfg.emb_dim, (
        "bottom MLP must end at emb_dim so its output joins the interaction")
    params = {
        "bottom": de.init_mlp(k_bot, (cfg.dense_features,) + cfg.bottom_mlp),
        "top": de.init_mlp(k_top, (top_mlp_in_dim(cfg),) + cfg.top_mlp),
    }
    if cfg.heterogeneous:
        specs = member_specs(cfg)
        keys = jax.random.split(k_arena, 2 * cfg.n_tables)
        params["tables"] = tuple(
            se.init_arena(keys[t], sp, shards)
            for t, sp in enumerate(specs))
        # per-table projection into the shared interaction width: table
        # t's reduced (dim_t,) bag joins the feature interaction as a
        # (emb_dim,) vector
        params["proj"] = tuple(
            (jax.random.normal(keys[cfg.n_tables + t],
                               (sp.dim, cfg.emb_dim), jnp.float32)
             / jnp.sqrt(sp.dim)).astype(cfg.dtype)
            for t, sp in enumerate(specs))
    else:
        params["arena"] = se.init_arena(k_arena, arena_spec(cfg), shards)
    return params


def group_source(params: Dict, cfg: DLRMConfig,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 axis: str = "model") -> es.TableGroupSource:
    """The default serving group of a heterogeneous config: one fp member
    per table arena, row-sharded when a mesh with a >1 axis is given."""
    assert cfg.heterogeneous, "group_source needs a heterogeneous config"
    return es.TableGroupSource.from_arenas(params["tables"],
                                           member_specs(cfg), mesh, axis)


def project_tables(proj, emb: jax.Array) -> jax.Array:
    """Per-table output projections: (B, T, dmax) padded group embeddings
    -> (B, T, emb_dim) interaction features. Table t consumes only its
    own leading dim_t lanes (the zero-padded tail contributes nothing and
    its projection rows receive zero gradient)."""
    cols = [emb[:, t, :p.shape[0]].astype(p.dtype) @ p
            for t, p in enumerate(proj)]
    return jnp.stack(cols, axis=1)


def _served_head(params: Dict, dense: jax.Array, emb: jax.Array,
                 mesh, source) -> jax.Array:
    """``head_logits`` on each device of the serving mesh (the sharded
    source's, when no mesh is passed): the head's kernels run per device
    on the replicated batch, and only the MLP weights enter."""
    mesh = mesh if mesh is not None else getattr(source, "mesh", None)
    mlp = {k: params[k] for k in ("bottom", "top")}
    return es.per_device(head_logits, mesh)(mlp, dense, emb)


def head_logits(mlp_params: Dict, dense: jax.Array,
                emb: jax.Array) -> jax.Array:
    """The DLRM head shared by every forward AND training path: reduced
    embeddings (B, T, D) + dense features -> logits (B,). One definition,
    so the trained network and the served network cannot drift apart.

    The ``obs_stage`` scopes are metadata-only (jax.named_scope +
    profiler TraceAnnotation when enabled, a shared null context when
    not) — the compiled HLO is identical either way, pinned by
    tests/test_obs.py."""
    with obs_stage("interaction"):
        bot = de.mlp_apply(mlp_params["bottom"], dense)
        x, _ = de.feature_interaction(bot, emb.astype(bot.dtype))
    with obs_stage("mlp"):
        return de.mlp_apply(mlp_params["top"], x)[:, 0]


def _legacy_source(params: Dict, mesh, cache, quantized,
                   axis: str = "model") -> es.EmbeddingSource:
    """Map the deprecated (mesh, cache, quantized) kwarg soup onto an
    EmbeddingSource (cache/quantized warn; mesh alone is the default
    sharded construction, not deprecated)."""
    if cache is not None or quantized is not None:
        warnings.warn(
            "dlrm forward kwargs cache=/quantized= are deprecated; pass "
            "source=<EmbeddingSource> instead (see the README migration "
            "table)", DeprecationWarning, stacklevel=3)
    return _compose_legacy(params, mesh, cache, quantized, axis)


def _compose_legacy(params: Dict, mesh, cache, quantized,
                    axis: str = "model") -> es.EmbeddingSource:
    assert "arena" in params, (
        "the legacy cache=/quantized= kwargs only compose over the "
        "uniform params['arena']; heterogeneous (table-group) params "
        "take source=<TableGroupSource>")
    # legacy contract: quantized only ever applied to the CACHED cold
    # pass; without a cache it was ignored (fp arena served)
    if cache is not None and quantized is not None:
        cold: es.EmbeddingSource = es.QuantizedArena(q=quantized[0],
                                                     scales=quantized[1])
        if se.mesh_shards(mesh, axis) > 1:
            cold = es.ShardedArena(cold, mesh, axis)
    else:
        cold = es.resolve_source(params["arena"], mesh, axis)
    return cold if cache is None else es.CachedSource(hot=cache, cold=cold)


def forward(params: Dict, cfg: DLRMConfig, dense: jax.Array,
            indices: jax.Array,
            mesh: Optional[jax.sharding.Mesh] = None, *,
            source: Optional[es.EmbeddingSource] = None) -> jax.Array:
    """dense: (B, dense_features); indices: (B, T, L) -> logits (B,).

    The sparse stage is ``embedding_source.lookup_fixed`` over `source`
    (default: the fp arena in `params`, row-sharded when a mesh is given).
    The graph is deliberately structured so the sparse stage (gather+psum)
    and the bottom-MLP GEMMs have no data dependence: on TPU the async
    collective combine of embedding shards overlaps the dense compute —
    the Centaur sparse/dense concurrency, expressed at the XLA level.
    """
    spec = arena_spec(cfg)
    if source is None:
        source = (group_source(params, cfg, mesh) if cfg.heterogeneous
                  else es.resolve_source(params["arena"], mesh))
    with obs_stage("sparse_lookup"):
        emb = es.lookup_fixed(source, spec, indices)  # sparse stage
        if cfg.heterogeneous:
            emb = project_tables(params["proj"], emb)
    return _served_head(params, dense, emb, mesh, source)   # dense stage


def forward_ragged(params: Dict, cfg: DLRMConfig, dense: jax.Array,
                   indices: jax.Array, offsets: jax.Array, *, max_l: int,
                   source: Optional[es.EmbeddingSource] = None,
                   mesh: Optional[jax.sharding.Mesh] = None,
                   cache: Optional[se.HotRowCache] = None,
                   quantized=None) -> jax.Array:
    """Ragged-bag forward: the production SparseLengthsSum path.

    dense: (B, dense_features); indices: flat per-table row-id stream (N,),
    possibly padded; offsets: (B*T+1,) ragged bag boundaries in (sample,
    table) row-major order; max_l: static per-bag length bound.

    The embedding stage is ``embedding_source.lookup_bags`` over `source`
    — ANY composition (fp / int8 / sharded / hot-cached / table-grouped)
    through the one entry point; serving-time path selection
    (MP-Rec-style) is the choice of source *value*, not of function.
    source=None defaults to the fp arena in `params` (or, on a
    heterogeneous config, the group over ``params['tables']``),
    row-sharded over the mesh's 'model' axis when a mesh is given. The
    legacy cache=/quantized= kwargs are deprecated shims onto the
    equivalent CachedSource/QuantizedArena.

    Per-table streams: with a ``TableGroupSource``, `indices`/`offsets`
    may instead be *sequences* — table t's own flat stream and (B+1,)
    offsets (each table keeps its own padding budget; `max_l` may be
    per-table too). Heterogeneous configs additionally project each
    table's reduced bag into the shared interaction width through
    ``params['proj']``.
    """
    spec = arena_spec(cfg)
    per_table = isinstance(indices, (tuple, list))
    if source is None:
        if cfg.heterogeneous:
            if cache is not None or quantized is not None:
                raise ValueError(
                    "the legacy cache=/quantized= kwargs cannot express "
                    "per-table composition — pass source=<TableGroup"
                    "Source> (see dlrm.table_plans / SourceSpec.tables)")
            source = group_source(params, cfg, mesh)
        else:
            source = _legacy_source(params, mesh, cache, quantized)
    elif cache is not None or quantized is not None:
        raise ValueError(
            "forward_ragged got BOTH source= and the deprecated cache=/"
            "quantized= kwargs — the legacy kwargs would be silently "
            "ignored; compose them into the source instead")
    with obs_stage("sparse_lookup"):
        if per_table:
            assert isinstance(source, es.TableGroupSource), (
                "per-table index/offset streams are the table-group "
                f"layout; got a {type(source).__name__} source")
            emb = es.lookup_bags_per_table(source, indices, offsets,
                                           max_l=max_l)
        else:
            emb = es.lookup_bags(source, spec, indices, offsets,
                                 max_l=max_l)
        if cfg.heterogeneous:
            emb = project_tables(params["proj"], emb)
    return _served_head(params, dense, emb, mesh, source)


def _bce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_sigmoid(logits)
    lognp = jax.nn.log_sigmoid(-logits)
    return -(labels * logp + (1 - labels) * lognp).mean()


def loss_fn(params: Dict, cfg: DLRMConfig, dense: jax.Array,
            indices: jax.Array, labels: jax.Array,
            mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    """Binary cross-entropy on click labels."""
    return _bce(forward(params, cfg, dense, indices, mesh), labels)


def loss_ragged(params: Dict, cfg: DLRMConfig, dense: jax.Array,
                indices: jax.Array, offsets: jax.Array, labels: jax.Array,
                *, max_l: int,
                mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    """BCE over the ragged production path — differentiable on every
    kernel backend via the sparse_lengths_sum custom VJP."""
    logits = forward_ragged(params, cfg, dense, indices, offsets,
                            max_l=max_l, mesh=mesh)
    return _bce(logits, labels)


def make_optimizer(cfg: DLRMConfig, lr: float = 1e-3):
    if cfg.heterogeneous:
        return partitioned({"tables": rowwise_adagrad(lr * 10)}, adamw(lr))
    return partitioned({"arena": rowwise_adagrad(lr * 10)}, adamw(lr))


def make_train_step(cfg: DLRMConfig, optimizer=None,
                    mesh: Optional[jax.sharding.Mesh] = None):
    opt = optimizer or make_optimizer(cfg)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, cfg, batch["dense"], batch["indices"], batch["labels"],
            mesh)
        new_params, new_state = opt.update(grads, opt_state, params)
        return new_params, new_state, loss

    return opt, train_step


def make_train_step_ragged(cfg: DLRMConfig, *, max_l: int, lr: float = 1e-3,
                           sparse: bool = True,
                           mesh: Optional[jax.sharding.Mesh] = None,
                           sharded: Optional[bool] = None,
                           axis: str = "model"):
    """Train step over ragged batches {dense, indices, offsets, labels}.

    Returns (opt_like, step) where step(params, opt_state, batch) ->
    (new_params, new_opt_state, loss, touched_rows); touched_rows (N,) are
    the unique arena rows the batch updated (fill = null row), which the
    online trainer feeds to the hot-cache write-through invalidation.

    sparse=True composes the row-wise *sparse* optimizer on the arena
    (update cost O(N) in the index-stream length, no densified (V, D)
    gradient) with AdamW on the MLPs; sparse=False is the dense-gradient
    baseline (jax.grad through the whole model + partitioned row-wise
    Adagrad), kept for the bench comparison.

    sharded=True (the default whenever sparse=True and `mesh` has a >1
    `axis`) runs the whole sparse step inside shard_map: the arena and its
    Adagrad accumulator live row-sharded over `axis`, the forward reduces
    shard-local partial bags (one psum of reduced D-vectors, never raw
    rows), each shard applies exactly the row updates it owns (null row
    excluded), and MLP grads are psum-combined so every replica steps in
    lockstep. Exact vs the replicated sparse step and the dense-grad
    baseline.
    """
    from repro.training import sparse_optim as so

    spec = arena_spec(cfg)
    if cfg.heterogeneous:
        if sharded or se.mesh_shards(mesh, axis) > 1:
            raise ValueError(
                "sharded TRAINING of a heterogeneous table group is not "
                "supported yet — serve groups sharded (ShardedArena "
                "members) and train replicated")
        return _make_train_step_group(cfg, spec, max_l=max_l, lr=lr,
                                      sparse=sparse)
    if sharded is None:
        sharded = sparse and se.mesh_shards(mesh, axis) > 1
    if sharded:
        if not sparse:
            raise ValueError("sharded=True is the sparse-optimizer path; "
                             "the dense-grad baseline threads the mesh "
                             "through the default sharded source instead")
        if mesh is None or axis not in mesh.axis_names:
            raise ValueError(f"sharded=True needs a mesh with axis "
                             f"{axis!r}")
        return _make_train_step_ragged_sharded(cfg, spec, max_l=max_l,
                                               lr=lr, mesh=mesh, axis=axis)
    if sparse and mesh is not None and se.mesh_shards(mesh, axis) > 1:
        raise ValueError(
            "sparse ragged training on a mesh must be sharded — the "
            "replicated sparse branch would silently train a per-device "
            "arena copy; pass sharded=True (or leave sharded=None)")
    if not sparse:
        opt = make_optimizer(cfg, lr)

        def dense_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_ragged)(
                params, cfg, batch["dense"], batch["indices"],
                batch["offsets"], batch["labels"], max_l=max_l, mesh=mesh)
            new_params, new_state = opt.update(grads, opt_state, params)
            flat = se.flatten_ragged_indices(spec, batch["indices"],
                                             batch["offsets"])
            rows, _ = jnp.unique(flat, size=flat.shape[0],
                                 fill_value=spec.null_row,
                                 return_inverse=True)
            return new_params, new_state, loss, rows.astype(jnp.int32)

        return opt, dense_step

    arena_opt = so.sparse_rowwise_adagrad(lr * 10)
    mlp_opt = adamw(lr)

    def init(params):
        return {"arena": arena_opt.init(params["arena"]),
                "mlp": mlp_opt.init({k: v for k, v in params.items()
                                     if k != "arena"})}

    def step(params, opt_state, batch):
        n_bags = batch["offsets"].shape[0] - 1
        # Forward the sparse stage once through the unified entry point;
        # its VJP w.r.t. the arena is a pure scatter of the bag gradients,
        # which the row-wise path applies directly — the arena never
        # enters autodiff (stop_gradient), so the update stays O(N).
        emb = es.lookup_bags(
            es.FpArena(jax.lax.stop_gradient(params["arena"])), spec,
            batch["indices"], batch["offsets"], max_l=max_l)

        def head(mlp_params, emb):
            return _bce(head_logits(mlp_params, batch["dense"], emb),
                        batch["labels"])

        mlp_params = {k: v for k, v in params.items() if k != "arena"}
        loss, (d_mlp, d_emb) = jax.value_and_grad(head, argnums=(0, 1))(
            mlp_params, emb)

        d_bags = d_emb.reshape(n_bags, spec.dim)
        with obs_stage("sparse_update"):
            rows, row_g = so.source_row_grads(spec, d_bags,
                                              batch["indices"],
                                              batch["offsets"])
            new_arena, arena_state = arena_opt.update(
                params["arena"], opt_state["arena"], rows, row_g)
        with obs_stage("dense_update"):
            new_mlp, mlp_state = mlp_opt.update(d_mlp, opt_state["mlp"],
                                                mlp_params)
        new_params = dict(new_mlp)
        new_params["arena"] = new_arena
        return new_params, {"arena": arena_state, "mlp": mlp_state}, \
            loss, rows

    return Optimizer(init, None), step


def _make_train_step_ragged_sharded(cfg: DLRMConfig, spec: se.ArenaSpec, *,
                                    max_l: int, lr: float,
                                    mesh: jax.sharding.Mesh, axis: str):
    """Row-sharded sparse train step (see make_train_step_ragged).

    Everything runs per-shard inside one shard_map: the only cross-chip
    traffic per step is the psum of reduced bag partials (forward) and the
    psum of MLP grads (backward) — row gradients never leave the shard
    that owns the rows, which is what keeps the update O(index stream)
    at any shard count.
    """
    from jax.sharding import PartitionSpec as P

    from repro.training import sparse_optim as so

    arena_opt = so.sparse_rowwise_adagrad(lr * 10)
    mlp_opt = adamw(lr)
    null = spec.null_row
    arena_state_spec = {"acc": P(axis, None), "step": P()}

    def init(params):
        return {"arena": arena_opt.init(params["arena"]),
                "mlp": mlp_opt.init({k: v for k, v in params.items()
                                     if k != "arena"})}

    def local_step(arena_shard, arena_state, mlp_params, mlp_state, batch):
        lo, vlocal = se.shard_row_range(arena_shard, axis)
        flat = se.flatten_ragged_indices(spec, batch["indices"],
                                         batch["offsets"])
        n_bags = batch["offsets"].shape[0] - 1
        b = n_bags // spec.n_tables
        emb = se.ragged_partial_reduce(jax.lax.stop_gradient(arena_shard),
                                       flat, batch["offsets"], axis)
        emb = emb.reshape(b, spec.n_tables, spec.dim) \
            .astype(arena_shard.dtype)

        def head(mlp_params, emb):
            return _bce(head_logits(mlp_params, batch["dense"], emb),
                        batch["labels"])

        loss, (d_mlp, d_emb) = jax.value_and_grad(head, argnums=(0, 1))(
            mlp_params, emb)
        # the batch is replicated over the model axis, so per-shard MLP
        # grads are already equal; the psum/N keeps replicas in lockstep
        # under non-deterministic reductions and is where a data-parallel
        # batch axis would combine partials
        d_mlp = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, axis), d_mlp)

        d_bags = d_emb.reshape(n_bags, spec.dim)
        with obs_stage("sparse_update"):
            rows, row_g = so.ragged_row_grads(d_bags, flat,
                                              batch["offsets"],
                                              fill_row=null)
            lrows, lg = so.shard_local_rows(rows, row_g, lo=lo,
                                            vlocal=vlocal, null_row=null)
            new_shard, new_arena_state = arena_opt.update(
                arena_shard, arena_state, lrows, lg)
        with obs_stage("dense_update"):
            new_mlp, new_mlp_state = mlp_opt.update(d_mlp, mlp_state,
                                                    mlp_params)
        return new_shard, new_arena_state, new_mlp, new_mlp_state, loss, \
            rows

    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(axis, None), arena_state_spec, P(), P(), P()),
        out_specs=(P(axis, None), arena_state_spec, P(), P(), P(), P()),
        check_vma=False,
    )

    def step(params, opt_state, batch):
        mlp_params = {k: v for k, v in params.items() if k != "arena"}
        new_arena, arena_state, new_mlp, mlp_state, loss, rows = fn(
            params["arena"], opt_state["arena"], mlp_params,
            opt_state["mlp"], batch)
        new_params = dict(new_mlp)
        new_params["arena"] = new_arena
        return new_params, {"arena": arena_state, "mlp": mlp_state}, \
            loss, rows

    return Optimizer(init, None), step


def _make_train_step_group(cfg: DLRMConfig, spec: se.ArenaSpec, *,
                           max_l: int, lr: float, sparse: bool):
    """Heterogeneous (table-group) ragged train step.

    sparse=True: the per-table row-wise path — the group lookup runs over
    stop-gradient arenas, the head (projections + MLPs) backprops
    normally, and ``sparse_optim.group_row_grads`` turns the padded bag
    gradient into per-table (rows, grads) pairs that per-table Adagrad
    accumulators apply in O(index stream) per table. sparse=False is the
    dense-grad baseline: autodiff straight through the group source
    (every member arena gets a densified gradient) + partitioned
    row-wise Adagrad — kept for the exactness comparison.

    step(params, opt_state, batch) -> (new_params, new_opt_state, loss,
    touched) where `touched` is the per-table tuple of touched-row arrays
    (fill = that table's null row), feeding per-table hot-cache
    write-through.
    """
    from repro.training import sparse_optim as so

    specs = member_specs(cfg)

    def touched_rows(batch):
        n = batch["indices"].shape[0]
        table, valid = se.ragged_position_tables(batch["offsets"], n,
                                                 cfg.n_tables)
        out = []
        for t, sp in enumerate(specs):
            idx_t = jnp.where(valid & (table == t), batch["indices"],
                              jnp.asarray(sp.null_row,
                                          batch["indices"].dtype))
            rows, _ = jnp.unique(idx_t, size=n, fill_value=sp.null_row,
                                 return_inverse=True)
            out.append(rows.astype(jnp.int32))
        return tuple(out)

    if not sparse:
        opt = make_optimizer(cfg, lr)

        def dense_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_ragged)(
                params, cfg, batch["dense"], batch["indices"],
                batch["offsets"], batch["labels"], max_l=max_l)
            new_params, new_state = opt.update(grads, opt_state, params)
            return new_params, new_state, loss, touched_rows(batch)

        return opt, dense_step

    arena_opt = so.group_rowwise_adagrad(lr * 10)
    mlp_opt = adamw(lr)

    def init(params):
        return {"tables": arena_opt.init(params["tables"]),
                "mlp": mlp_opt.init({k: v for k, v in params.items()
                                     if k != "tables"})}

    def step(params, opt_state, batch):
        n_bags = batch["offsets"].shape[0] - 1
        group = es.TableGroupSource(
            members=tuple(es.FpArena(jax.lax.stop_gradient(a))
                          for a in params["tables"]),
            specs=specs)
        emb = es.lookup_bags(group, spec, batch["indices"],
                             batch["offsets"], max_l=max_l)

        def head(head_params, emb):
            proj_emb = project_tables(head_params["proj"], emb)
            return _bce(head_logits(head_params, batch["dense"],
                                    proj_emb), batch["labels"])

        head_params = {k: v for k, v in params.items() if k != "tables"}
        loss, (d_head, d_emb) = jax.value_and_grad(head, argnums=(0, 1))(
            head_params, emb)

        d_bags = d_emb.reshape(n_bags, spec.dim)
        with obs_stage("sparse_update"):
            per_table = so.group_row_grads(specs, d_bags,
                                           batch["indices"],
                                           batch["offsets"], max_l=max_l)
            new_tables, tables_state = arena_opt.update(
                params["tables"], opt_state["tables"], per_table)
        with obs_stage("dense_update"):
            new_head, mlp_state = mlp_opt.update(d_head, opt_state["mlp"],
                                                 head_params)
        new_params = dict(new_head)
        new_params["tables"] = new_tables
        return new_params, {"tables": tables_state, "mlp": mlp_state}, \
            loss, tuple(rows for rows, _ in per_table)

    return Optimizer(init, None), step


def make_serve_step(cfg: DLRMConfig,
                    mesh: Optional[jax.sharding.Mesh] = None):
    def serve_step(params, batch):
        return jax.nn.sigmoid(
            forward(params, cfg, batch["dense"], batch["indices"], mesh))
    return serve_step


def make_ragged_serve_step(cfg: DLRMConfig, *, max_l: int,
                           mesh: Optional[jax.sharding.Mesh] = None,
                           cache: Optional[se.HotRowCache] = None,
                           quantized=None):
    """Serve step over ragged batches ({dense, indices, offsets} -> CTR).

    The embedding source is a call-time pytree argument — that is how the
    serving engine swaps in a new version of ANY source component (hot
    cache, quantized cold arena, the full fp arena) without recompiling:
    same treedef + same leaf shapes = same compiled executable. With
    source=None the fp arena in `params` serves (mesh-sharded when given).

    Back-compat shims (both warn): the legacy build-time cache=/quantized=
    kwargs, and a bare HotRowCache passed as the per-call third argument
    (the pre-API calling convention) — each is composed into the
    equivalent CachedSource.
    """
    if cache is not None or quantized is not None:
        warnings.warn(
            "make_ragged_serve_step kwargs cache=/quantized= are "
            "deprecated; pass source=<EmbeddingSource> per call instead",
            DeprecationWarning, stacklevel=2)
    default_cache, default_q = cache, quantized

    def serve_step(params, batch, source=None):
        if source is None and default_cache is not None:
            source = _legacy_source(params, mesh, default_cache,
                                    default_q)
        elif isinstance(source, se.HotRowCache):
            warnings.warn(
                "passing a bare HotRowCache to the serve step is "
                "deprecated; pass a CachedSource (or any "
                "EmbeddingSource) instead", DeprecationWarning,
                stacklevel=2)
            # honor the build-time quantized= arena exactly like the
            # legacy cached_q path did for per-call cache swaps
            source = _compose_legacy(params, mesh, source, default_q)
        return jax.nn.sigmoid(forward_ragged(
            params, cfg, batch["dense"], batch["indices"],
            batch["offsets"], max_l=max_l, mesh=mesh, source=source))
    return serve_step


def make_ragged_serve_stages(cfg: DLRMConfig, *, max_l: int,
                             mesh: Optional[jax.sharding.Mesh] = None):
    """The serve step split at its pipeline-stage boundaries — the live
    Fig-5 characterization mode.

    Returns ``(sparse_stage, interact_stage, top_stage)``; composed they
    compute exactly what ``make_ragged_serve_step`` computes (pinned by
    tests/test_obs.py), but jitting each separately lets the serving
    engine sync between stages and attribute *device* time to the
    embedding stage vs. the dense stages — the paper's Fig-5
    embedding-vs-MLP split, measured on live traffic instead of offline
    microbenchmarks:

      * ``sparse_stage(params, batch, source)`` -> (B, T, D) reduced
        bags (plus the per-table projections on heterogeneous configs —
        the same scope ``obs_stage('sparse_lookup')`` covers in the
        fused step);
      * ``interact_stage(params, batch, emb)`` -> interaction features
        (bottom MLP + feature interaction);
      * ``top_stage(params, x)`` -> CTR probabilities (top MLP +
        sigmoid).

    ``mesh`` is accepted for signature symmetry with
    ``make_ragged_serve_step``; the source is always explicit here so it
    never feeds a default-source resolution.
    """
    del mesh
    spec = arena_spec(cfg)

    def sparse_stage(params, batch, source):
        with obs_stage("sparse_lookup"):
            emb = es.lookup_bags(source, spec, batch["indices"],
                                 batch["offsets"], max_l=max_l)
            if cfg.heterogeneous:
                emb = project_tables(params["proj"], emb)
        return emb

    def interact_stage(params, batch, emb):
        with obs_stage("interaction"):
            bot = de.mlp_apply(params["bottom"], batch["dense"])
            x, _ = de.feature_interaction(bot, emb.astype(bot.dtype))
        return x

    def top_stage(params, x):
        with obs_stage("mlp"):
            return jax.nn.sigmoid(de.mlp_apply(params["top"], x)[:, 0])

    return sparse_stage, interact_stage, top_stage
