"""Training launcher: DLRM (the paper's workload) and any assigned LM arch.

    PYTHONPATH=src python -m repro.launch.train --arch dlrm1 --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch dlrm1 --steps 200 \
        --ragged --online-cache          # online ragged training + live cache
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --smoke --steps 50 --ckpt-dir /tmp/ckpt --resume

Production runs pass --mesh pod|multipod (256/512 chips); CPU runs use the
reduced smoke configs. Fault tolerance: periodic async checkpoints, resume
with --resume, straggler monitor logging.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs.dlrm import DLRM_CONFIGS, DLRM_SMOKE
from repro.configs.registry import ARCHS, SMOKE_ARCHS
from repro.core import dlrm as dlrm_mod
from repro.data import DLRMSynthetic, LMSynthetic
from repro.distributed.fault_tolerance import StragglerMonitor
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import api


def train_dlrm_ragged(args) -> float:
    """Online ragged training: row-wise sparse optimizer + (optionally) a
    live hot-row cache that re-ranks itself from the decayed histogram."""
    from repro.training import OnlineCacheConfig, OnlineTrainer

    from repro.distributed.sharding import place_row_sharded

    cfg = DLRM_SMOKE if args.smoke else DLRM_CONFIGS[args.arch]
    mesh = _mesh(args)
    key = jax.random.PRNGKey(args.seed)
    shards = mesh.shape["model"] if mesh else 1
    params = dlrm_mod.init(key, cfg, shards)
    # the arena *lives* row-sharded: the sharded train step and the sharded
    # serving cold pass both consume it in place, no per-step reshard
    params["arena"] = place_row_sharded(params["arena"], mesh)
    max_l = 2 * cfg.lookups_per_table
    cache_cfg = None
    if args.online_cache:
        cache_cfg = OnlineCacheConfig(k=args.cache_k,
                                      refresh_every=args.cache_refresh,
                                      quantize_cold=args.quantize_cold)
    telemetry = obs.Telemetry(tracing=args.trace)
    if args.trace:
        obs.enable_stage_annotations(True)
    trainer = OnlineTrainer(cfg, params, max_l=max_l,
                            sparse=not args.dense_grads,
                            cache_cfg=cache_cfg, mesh=mesh,
                            telemetry=telemetry)
    data = DLRMSynthetic(cfg, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    mon = StragglerMonitor()
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        (trainer.params, trainer.opt_state), _ = ckpt.restore(
            (trainer.params, trainer.opt_state))
        start = ckpt.latest_step() + 1
        print(f"resumed from step {start - 1}")

    pad_to = args.batch_size * cfg.n_tables * max_l
    loss = float("nan")
    for step in range(start, args.steps):
        t0 = time.time()
        batch = data.ragged_batch(args.batch_size, max_l=max_l,
                                  pad_to=pad_to)
        loss = trainer.train_step(batch)
        mon.record(step, time.time() - t0)
        if step % args.log_every == 0:
            extra = (f" cache v{trainer.version}" if args.online_cache
                     else "")
            if args.quantize_cold and trainer.cold_q is not None:
                extra += f" dirty_q={int(trainer._dirty_q.sum())}"
            print(f"step {step:5d} loss {loss:.4f} "
                  f"({time.time() - t0:.3f}s){extra}")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step, (trainer.params, trainer.opt_state))
    if ckpt:
        ckpt.wait()
    print(f"final loss {loss:.4f} "
          f"(straggler events: {len(mon.events)})")
    if args.metrics_json:
        _dump_metrics(telemetry, args.metrics_json)
    return loss


def _dump_metrics(telemetry, path: str) -> None:
    """Write the registry snapshot (+ swap events) as one JSON file."""
    import json

    with open(path, "w") as f:
        json.dump(telemetry.snapshot(), f, indent=2, default=str)
    print(f"metrics snapshot -> {path}")


def train_dlrm(args) -> float:
    if args.ragged:
        return train_dlrm_ragged(args)
    cfg = DLRM_SMOKE if args.smoke else DLRM_CONFIGS[args.arch]
    mesh = _mesh(args)
    key = jax.random.PRNGKey(args.seed)
    shards = mesh.shape["model"] if mesh else 1
    params = dlrm_mod.init(key, cfg, shards)
    opt, step_fn = dlrm_mod.make_train_step(cfg, mesh=mesh)
    opt_state = opt.init(params)
    step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    data = DLRMSynthetic(cfg, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    mon = StragglerMonitor()
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        (params, opt_state), _ = ckpt.restore((params, opt_state))
        start = ckpt.latest_step() + 1
        print(f"resumed from step {start - 1}")

    loss = float("nan")
    for step in range(start, args.steps):
        t0 = time.time()
        batch = {k: jnp.asarray(v)
                 for k, v in data.batch(args.batch_size).items()}
        params, opt_state, loss = step_jit(params, opt_state, batch)
        mon.record(step, time.time() - t0)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"({time.time() - t0:.3f}s)")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step, (params, opt_state))
    if ckpt:
        ckpt.wait()
    print(f"final loss {float(loss):.4f} "
          f"(straggler events: {len(mon.events)})")
    return float(loss)


def train_lm(args) -> float:
    cfg = (SMOKE_ARCHS if args.smoke else ARCHS)[args.arch]
    mesh = _mesh(args)
    key = jax.random.PRNGKey(args.seed)
    params, _ = api.init(key, cfg)
    opt_name, opt, step_fn = api.make_train_step(cfg, mesh=mesh)
    opt_state = opt.init(params)
    step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    data = LMSynthetic(cfg, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    mon = StragglerMonitor()
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        (params, opt_state), _ = ckpt.restore((params, opt_state))
        start = ckpt.latest_step() + 1
        print(f"resumed from step {start - 1}")

    loss = float("nan")
    for step in range(start, args.steps):
        t0 = time.time()
        raw = data.batch(args.batch_size, args.seq_len)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if "frames" in batch:
            batch["frames"] = batch["frames"].astype(jnp.bfloat16)
        if "patches" in batch:
            batch["patches"] = batch["patches"].astype(jnp.bfloat16)
        params, opt_state, metrics = step_jit(params, opt_state, batch)
        loss = metrics["loss"]
        mon.record(step, time.time() - t0)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({time.time() - t0:.3f}s)")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step, (params, opt_state))
    if ckpt:
        ckpt.wait()
    print(f"final loss {float(loss):.4f} "
          f"(straggler events: {len(mon.events)})")
    return float(loss)


def _mesh(args):
    if getattr(args, "shards", 1) > 1:
        if args.mesh != "none":
            raise SystemExit(
                "--shards builds its own N-way 'model' mesh and cannot be "
                "combined with --mesh pod/multipod (the production meshes "
                "fix their own model-axis width); pass one or the other")
        if len(jax.devices()) < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs {args.shards} devices; this "
                f"host has {len(jax.devices())}")
        from repro.launch.mesh import make_mesh
        return make_mesh((args.shards,), ("model",))
    if args.mesh == "none":
        return None
    return make_production_mesh(multi_pod=(args.mesh == "multipod"))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="dlrm1",
                   help="dlrm1..dlrm6 or an assigned LM arch id")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced config (CPU-runnable)")
    p.add_argument("--mesh", default="none",
                   choices=("none", "pod", "multipod"))
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--ragged", action="store_true",
                   help="DLRM: train on ragged SparseLengthsSum batches "
                        "with the row-wise sparse optimizer")
    p.add_argument("--online-cache", action="store_true",
                   help="with --ragged: maintain a live versioned hot-row "
                        "cache from the decayed trace histogram")
    p.add_argument("--dense-grads", action="store_true",
                   help="with --ragged: densified-gradient baseline "
                        "instead of the row-wise sparse optimizer")
    p.add_argument("--cache-k", type=int, default=2048)
    p.add_argument("--cache-refresh", type=int, default=50)
    p.add_argument("--quantize-cold", action="store_true",
                   help="with --online-cache: maintain an int8 cold "
                        "arena incrementally (only rows touched since "
                        "the last rebuild are re-quantized)")
    p.add_argument("--shards", type=int, default=1,
                   help="row-shard the embedding arena over an N-way "
                        "'model' mesh (DLRM; with --ragged the sparse "
                        "optimizer applies shard-local row updates)")
    p.add_argument("--metrics-json", default=None,
                   help="with --ragged: write the telemetry registry "
                        "snapshot (counters/gauges/histograms + swap "
                        "events) to this path at exit")
    p.add_argument("--trace", action="store_true",
                   help="with --ragged: collect host spans and enable "
                        "jax.profiler stage annotations in jitted code")
    args = p.parse_args()
    use_compile_cache()
    if args.arch.startswith("dlrm"):
        train_dlrm(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
