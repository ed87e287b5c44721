#!/usr/bin/env python3
"""Bring-up smoke run of the DLRM main path on a TPU.

    python chip_smoke.py [--arch dlrm1] [--requests 256] [--seed 0]
    python chip_smoke.py --chips 4          # the row-sharded path only

One chip: builds a Table I configuration (``--arch``, default dlrm1) at its
full width with ``dlrm.init`` (random weights from ``--seed``), serves
requests through ``RecEngine.submit``/``step`` on the ``ragged`` and
``cached`` sources after ``warmup()``, and checks every served CTR against a
plain float32 ``jax.numpy`` forward of the same params and requests. It then
takes ``OnlineTrainer`` steps on the ragged sparse path, and one dense-
gradient step (whose backward is the ``sls_grad_table`` kernel) that must
agree with the sparse step.

``--chips 4``: only the path that exists across chips. The arena (default
dlrm5) is row-sharded over a 4-way ``model`` mesh and served through
``source="sharded"`` against the replicated forward, and one sharded ragged
train step is compared with the unsharded one.

Refuses to run without a TPU. Every check that fails raises, so the script
exits non-zero; only a run whose checks all pass prints its last line,
``{"ok": true, "device": {...}}``. The numbers printed before it are a smoke
record (compile seconds, errors, bytes), not benchmark results.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.dlrm import DLRM_CONFIGS  # noqa: E402
from repro.core import dlrm  # noqa: E402
from repro.core import sparse_engine as se  # noqa: E402
from repro.data import DLRMSynthetic  # noqa: E402
from repro.distributed.sharding import place_row_sharded  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serving import RecEngine, requests_from_ragged_batch  # noqa: E402
from repro.training import OnlineTrainer  # noqa: E402

# Served CTR against the float32 reference: the kernels accumulate in f32
# in another order than the reference, nothing else differs.
CTR_TOL = 1e-4
# Dense-gradient step against the sparse step, and sharded against
# replicated: the same update rule over sums taken in another order.
STEP_TOL = 1e-5
BATCH = 32                   # requests per served micro-batch / train batch


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def make_traffic(cfg, n: int, seed: int):
    """n requests with Poisson bag lengths (mean ``lookups_per_table``,
    at most twice that), as the launcher trains on; returns the requests,
    their ragged batch and the static bag bound."""
    max_l = 2 * cfg.lookups_per_table
    rb = DLRMSynthetic(cfg, seed=seed).ragged_batch(n, max_l=max_l)
    return requests_from_ragged_batch(rb, cfg.n_tables), rb, max_l


def train_batches(cfg, n: int, seed: int, max_l: int):
    """n ragged training batches of one static shape (no recompiles)."""
    data = DLRMSynthetic(cfg, seed=seed)
    pad = BATCH * cfg.n_tables * max_l
    return [data.ragged_batch(BATCH, max_l=max_l, pad_to=pad)
            for _ in range(n)]


def _mlp(layers, x):
    for i, (w, b) in enumerate(layers):
        x = x @ w + b
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


@functools.partial(jax.jit, static_argnames=("n_tables",))
def _reference(params, dense, rows, bags, n_tables: int):
    b = dense.shape[0]
    emb = jax.ops.segment_sum(jnp.take(params["arena"], rows, axis=0), bags,
                              num_segments=b * n_tables)
    bot = _mlp(params["bottom"], dense)
    feats = jnp.concatenate([bot[:, None], emb.reshape(b, n_tables, -1)], 1)
    z = jnp.einsum("bfd,bgd->bfg", feats, feats)
    li, lj = np.tril_indices(n_tables + 1, -1)
    x = jnp.concatenate([bot, z[:, li, lj]], -1)
    return jax.nn.sigmoid(_mlp(params["top"], x)[:, 0])


def reference_ctr(params, cfg, reqs) -> np.ndarray:
    """The DLRM forward in plain float32 ``jax.numpy`` — gather, per-bag
    sum, MLPs, pairwise dots — sharing no code with the served path."""
    t = cfg.n_tables
    rows, bags = [], []
    for i, r in enumerate(reqs):
        for j, ids in enumerate(r.sparse_ids):
            rows.append(np.asarray(ids, np.int64) + j * cfg.rows_per_table)
            bags.append(np.full(len(ids), i * t + j, np.int64))
    dense = np.stack([r.dense for r in reqs])
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(
            params, jnp.asarray(dense), jnp.asarray(np.concatenate(rows)),
            jnp.asarray(np.concatenate(bags)), t))


def serve(cfg, params, reqs, source: str, max_l: int, *, mesh=None,
          trace=None, cache_k: int = 4096):
    """Serve ``reqs`` through RecEngine after warmup(); returns the engine,
    the served CTRs and the warmup (compile) seconds."""
    kw = {}
    if source == "cached":
        kw = dict(cache_k=cache_k, cache_trace=se.trace_row_counts(
            dlrm.arena_spec(cfg), trace["indices"], trace["offsets"]))
    engine = RecEngine(cfg, params, source=source, max_l=max_l,
                       max_batch=BATCH, buckets=(BATCH,), mesh=mesh, **kw)
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    for r in reqs:
        engine.submit(r)
    served = 0
    while served < len(reqs):
        served += engine.step(force=True)
    check(engine.served == len(reqs), f"{source}: served {engine.served}")
    return engine, np.array([r.prob for r in reqs]), compile_s


def served_hlo(engine, reqs) -> str:
    """The compiled serve step's HLO for one micro-batch."""
    batch, _ = engine._assemble(reqs[:BATCH], BATCH)
    return engine._serve.lower(engine.params, batch,
                               engine.source).compile().as_text()


def train(cfg, params, batches, max_l: int, *, sparse: bool = True):
    """OnlineTrainer steps over ``batches``; returns the trainer, the
    losses and the first step's seconds (its compile)."""
    tr = OnlineTrainer(cfg, params, max_l=max_l, sparse=sparse)
    t0 = time.perf_counter()
    tr.train_step(batches[0])
    compile_s = time.perf_counter() - t0
    for b in batches[1:]:
        tr.train_step(b)
    check(all(np.isfinite(tr.losses)), f"finite losses {tr.losses}")
    return tr, tr.losses, compile_s


def touched_rows(cfg, batch) -> np.ndarray:
    """Arena rows a ragged batch reads (its valid positions only)."""
    n = int(batch["offsets"][-1])
    bag = np.searchsorted(batch["offsets"][1:], np.arange(n), side="right")
    return np.unique(batch["indices"][:n].astype(np.int64)
                     + (bag % cfg.n_tables) * cfg.rows_per_table)


def device_bytes(devices, key: str) -> dict:
    """A ``memory_stats()`` counter per device (None where not reported)."""
    return {d.id: (d.memory_stats() or {}).get(key) for d in devices}


def max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def one_chip(cfg, n_req: int, seed: int) -> None:
    params = dlrm.init(jax.random.PRNGKey(seed), cfg)
    # the row kernels read the arena in the layout printed here, in place
    print(f"arena_bytes={params['arena'].nbytes} "
          f"shape={tuple(params['arena'].shape)} "
          f"layout={params['arena'].format.layout}")
    reqs, rb, max_l = make_traffic(cfg, n_req, seed)
    want = reference_ctr(params, cfg, reqs)
    for source in ("ragged", "cached"):
        engine, got, compile_s = serve(
            cfg, params, reqs, source, max_l, trace=rb,
            cache_k=min(4096, dlrm.arena_spec(cfg).total_rows // 8))
        kernels = "tpu_custom_call" in served_hlo(engine, reqs)
        err = max_err(got, want)
        print(f"serve source={source} compile_s={compile_s:.2f} "
              f"requests={len(reqs)} tpu_custom_call={kernels} "
              f"max_abs_err={err:.3e} tol={CTR_TOL}")
        # compiled Pallas kernels are custom calls; interpreted ones are not
        check(kernels or ops.get_impl() != "pallas",
              f"{source}: no kernel in the compiled serve step")
        check(err <= CTR_TOL, f"{source}: CTR error {err} > {CTR_TOL}")
        del engine

    batches = train_batches(cfg, 5, seed + 1, max_l)
    rows = touched_rows(cfg, batches[0])
    dense_tr, dense_loss, dense_s = train(cfg, params, batches[:1], max_l,
                                          sparse=False)
    dense_rows = np.asarray(dense_tr.params["arena"][rows])
    del dense_tr
    tr, losses, compile_s = train(cfg, params, batches[:1], max_l)
    step_err = max_err(np.asarray(tr.params["arena"][rows]), dense_rows)
    loss_err = abs(losses[0] - dense_loss[0])
    print(f"train dense_grad compile_s={dense_s:.2f} loss={dense_loss[0]:.6f}"
          f" sparse_vs_dense loss_err={loss_err:.3e} "
          f"row_err={step_err:.3e} tol={STEP_TOL}")
    check(loss_err <= STEP_TOL and step_err <= STEP_TOL,
          "dense-gradient step (sls_grad_table) disagrees with the sparse "
          "step")
    for b in batches[1:]:
        tr.train_step(b)
    check(all(np.isfinite(tr.losses)), f"finite losses {tr.losses}")
    print(f"train sparse compile_s={compile_s:.2f} steps={len(tr.losses)} "
          f"loss_first={tr.losses[0]:.6f} loss_last={tr.losses[-1]:.6f}")


def four_chips(cfg, n_req: int, seed: int) -> None:
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 sees {len(jax.devices())} devices")
    mesh = make_mesh((4,), ("model",), devices)
    params = dlrm.init(jax.random.PRNGKey(seed), cfg, shards=4)
    arena = params["arena"]
    sharded = dict(params, arena=place_row_sharded(arena, mesh))
    # once the copy is done, every init transient on device 0 is freed and
    # bytes_in_use shows the split alone
    jax.block_until_ready(sharded["arena"])
    arena.delete()
    shard_bytes = {s.device.id: s.data.nbytes
                   for s in sharded["arena"].addressable_shards}
    in_use = device_bytes(devices, "bytes_in_use")
    print(f"arena_bytes={sharded['arena'].nbytes} shard_bytes={shard_bytes} "
          f"bytes_in_use={in_use}")
    check(len(shard_bytes) == 4 and max(shard_bytes.values())
          <= sharded["arena"].nbytes // 4 + 1024, "arena not split 4 ways")

    reqs, _, max_l = make_traffic(cfg, n_req, seed)
    eng, got, compile_s = serve(cfg, sharded, reqs, "sharded", max_l,
                                mesh=mesh)
    hlo = served_hlo(eng, reqs)
    del eng
    replicated = dict(params, arena=jax.device_put(sharded["arena"],
                                                   devices[0]))
    _, want, _ = serve(cfg, replicated, reqs, "ragged", max_l)
    err = max_err(got, want)
    print(f"serve source=sharded compile_s={compile_s:.2f} "
          f"requests={len(reqs)} tpu_custom_call={'tpu_custom_call' in hlo} "
          f"all_reduce={'all-reduce' in hlo} "
          f"sharded_vs_replicated max_abs_err={err:.3e} tol={CTR_TOL}")
    check("all-reduce" in hlo, "sharded serve step has no cross-chip psum")
    check(err <= CTR_TOL, f"sharded CTR error {err} > {CTR_TOL}")

    batch = {k: jnp.asarray(v) for k, v in train_batches(
        cfg, 1, seed + 1, max_l)[0].items()
        if k in ("dense", "indices", "offsets", "labels")}
    outs = []
    for p, kw in ((replicated, {}), (sharded, dict(mesh=mesh, sharded=True))):
        opt, step = dlrm.make_train_step_ragged(cfg, max_l=max_l, **kw)
        t0 = time.perf_counter()
        new, _, loss, rows = jax.jit(step)(p, opt.init(p), batch)
        loss = float(loss)
        outs.append((loss, np.asarray(new["arena"][rows]),
                     time.perf_counter() - t0))
    (loss_r, rows_r, _), (loss_s, rows_s, step_s) = outs
    loss_err, row_err = abs(loss_r - loss_s), max_err(rows_r, rows_s)
    print(f"train sharded step_s={step_s:.2f} loss={loss_s:.6f} "
          f"loss_err={loss_err:.3e} row_err={row_err:.3e} tol={STEP_TOL}")
    check(np.isfinite(loss_s) and loss_err <= STEP_TOL
          and row_err <= STEP_TOL, "sharded train step != unsharded step")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--arch", choices=sorted(DLRM_CONFIGS),
                   help="Table I configuration (default dlrm1; dlrm5 with "
                        "--chips 4)")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--requests", type=int, default=256)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        print(f"chip_smoke: no JAX backend: {e}", file=sys.stderr)
        return 1
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    check(ops.get_impl() == "pallas", f"impl={ops.get_impl()}")
    cache = use_compile_cache()
    print(f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"impl={ops.get_impl()} compile_cache={cache}")
    arch = args.arch or ("dlrm5" if args.chips == 4 else "dlrm1")
    cfg = DLRM_CONFIGS[arch]
    print(f"arch={arch} tables={cfg.n_tables} rows={cfg.rows_per_table} "
          f"dim={cfg.emb_dim} top_in={dlrm.top_mlp_in_dim(cfg)}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(cfg, args.requests,
                                                  args.seed)
    peak = device_bytes(jax.devices()[:args.chips], "peak_bytes_in_use")
    print(f"peak_bytes_in_use={peak} wall_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
