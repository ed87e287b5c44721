"""Expert-parallel MoE — the Centaur sparse engine generalized.

MoE dispatch is a sparse gather/scatter over a parameter store far too big
for one chip — exactly the paper's embedding-table problem. The same design
answers it: shard the store (experts) over the 'model' axis, stream tokens to
the owning chip with a *fixed-capacity* all-to-all (static shapes = the
SRAM_sparseID prefetch buffer), compute locally, stream back, and reduce
(combine) on the fly at the source.

Token dim is temporarily sharded over **all** mesh axes inside the block
("EP borrows the TP axis"), so dispatch buffers scale 1/n_devices; with
top-8 and cf=1.25 the per-chip buffer stays ~10x the local token bytes
regardless of pod size.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.distributed.sharding import active_mesh
from repro.models.params import Builder


def init_moe(b: Builder, mcfg: MoEConfig, d: int):
    """Expert weights are sharded over BOTH the 'model' axis (expert dim,
    EP) and the 'data' axis (hidden dim, ZeRO-3/FSDP): a 1T-param MoE's
    expert block is 2 TB in bf16 — EP alone leaves 125 GB/chip on a 16-way
    model axis. The FSDP shard is re-gathered per layer inside the MoE
    shard_map (bf16 all-gather over 'data'), and its gradient reduce-
    scatters back automatically through autodiff."""
    e, ff = mcfg.n_experts, mcfg.expert_ff
    return {
        "wr": b.normal((d, e), (None, None), dtype=jnp.float32),
        "wg": b.normal((e, d, ff), ("expert", "fsdp", None)),
        "wu": b.normal((e, d, ff), ("expert", "fsdp", None)),
        "wd": b.normal((e, ff, d), ("expert", "fsdp", None)),
    }


def _capacity(t_local: int, mcfg: MoEConfig, ep: int) -> int:
    c = int(np.ceil(t_local * mcfg.top_k * mcfg.capacity_factor
                    / mcfg.n_experts))
    return max(8, ((c + 7) // 8) * 8)


def _route(xf32, wr, mcfg: MoEConfig):
    """Returns (weights (T,k), idx (T,k), probs (T,E))."""
    logits = xf32 @ wr
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, mcfg.top_k)
    w = jnp.take_along_axis(probs, idx, axis=-1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)   # renormalize
    return w, idx, probs


def _slots(idx, n_experts: int, capacity: int):
    """Per-choice dispatch slot = expert*C + rank-within-expert; OOB drops."""
    flat_e = idx.reshape(-1)                                # (T*k,)
    oh = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1         # rank in expert
    valid = pos < capacity
    slot = jnp.where(valid, flat_e * capacity + pos, n_experts * capacity)
    return slot, valid


def _expert_ffn(x, wg, wu, wd):
    """x: (E_loc, C', d) bf16; experts stacked on dim 0."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg)) \
        * jnp.einsum("ecd,edf->ecf", x, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _aux_loss(probs, idx, mcfg: MoEConfig):
    """Switch-style load-balance loss (local shard contribution)."""
    e = mcfg.n_experts
    frac = jax.nn.one_hot(idx.reshape(-1), e).mean(0)       # routed fraction
    imp = probs.mean(0)                                     # router mass
    return e * jnp.sum(frac * imp)


def _moe_shard(xl, wr, wg, wu, wd, *, mcfg: MoEConfig, ep_axis: str,
               all_axes: Tuple[str, ...], fsdp_axis: Optional[str] = None):
    """Runs inside shard_map. xl: (B_loc, S_loc, d) local tokens.

    The token flatten happens HERE (locally): flattening (B,S) -> (B*S) at
    the jax level merges two dims sharded on different mesh axes, whose flat
    index blocks are non-contiguous — GSPMD resolves that with a full
    rematerialization (measured: 3x 30 GB all-gathers of the GLOBAL
    activation per layer on the multi-pod kimi cell). A local reshape is
    free."""
    ep = jax.lax.axis_size(ep_axis)
    e_loc = mcfg.n_experts // ep
    b_loc, s_loc, d = xl.shape
    xl = xl.reshape(b_loc * s_loc, d)
    t_loc = b_loc * s_loc
    cap = _capacity(t_loc, mcfg, ep)

    if fsdp_axis:
        # ZeRO-3: re-materialize this shard's expert weights (bf16 gather
        # over the DP axes); grads reduce-scatter back via autodiff.
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=1, tiled=True)

    w, idx, probs = _route(xl.astype(jnp.float32), wr, mcfg)
    slot, valid = _slots(idx, mcfg.n_experts, cap)

    xrep = jnp.repeat(xl, mcfg.top_k, axis=0)               # (T*k, d)
    disp = jnp.zeros((mcfg.n_experts * cap, d), xl.dtype)
    disp = disp.at[slot].set(xrep, mode="drop")
    disp = disp.reshape(ep, e_loc * cap, d)

    # --- stream tokens to expert owners (fixed-capacity a2a) ---
    recv = jax.lax.all_to_all(disp, ep_axis, split_axis=0, concat_axis=0,
                              tiled=True)                   # (ep, E_loc*C, d)
    recv = recv.reshape(ep, e_loc, cap, d).transpose(1, 0, 2, 3) \
               .reshape(e_loc, ep * cap, d)

    y = _expert_ffn(recv, wg, wu, wd)

    # --- stream results back ---
    y = y.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3) \
         .reshape(ep, e_loc * cap, d)
    back = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                              tiled=True)
    back = back.reshape(mcfg.n_experts * cap, d)

    # --- on-the-fly combine (weighted reduce at the source) ---
    rows = jnp.take(back, jnp.minimum(slot, back.shape[0] - 1), axis=0)
    rows = jnp.where(valid[:, None], rows, 0)
    y_tok = (rows.reshape(t_loc, mcfg.top_k, d)
             * w[..., None].astype(rows.dtype)).sum(1)

    aux = _aux_loss(probs, idx, mcfg)
    aux = jax.lax.pmean(aux, all_axes)
    return y_tok.reshape(b_loc, s_loc, d).astype(xl.dtype), aux


def _moe_local(xf, p, mcfg: MoEConfig):
    """Single-shard path (no mesh): same math, ep=1, no collectives."""
    t, d = xf.shape
    cap = _capacity(t, mcfg, 1)
    w, idx, probs = _route(xf.astype(jnp.float32), p["wr"], mcfg)
    slot, valid = _slots(idx, mcfg.n_experts, cap)
    xrep = jnp.repeat(xf, mcfg.top_k, axis=0)
    disp = jnp.zeros((mcfg.n_experts * cap, d), xf.dtype)
    disp = disp.at[slot].set(xrep, mode="drop")
    y = _expert_ffn(disp.reshape(mcfg.n_experts, cap, d),
                    p["wg"], p["wu"], p["wd"])
    back = y.reshape(mcfg.n_experts * cap, d)
    rows = jnp.take(back, jnp.minimum(slot, back.shape[0] - 1), axis=0)
    rows = jnp.where(valid[:, None], rows, 0)
    y_tok = (rows.reshape(t, mcfg.top_k, d)
             * w[..., None].astype(rows.dtype)).sum(1)
    return y_tok.astype(xf.dtype), _aux_loss(probs, idx, mcfg)


def apply_moe(p, mcfg: MoEConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y (B, S, D), aux_loss scalar).

    Tokens enter the shard_map 3D (B over the DP axes, S over 'model' — the
    SP layout) and are flattened locally inside; see _moe_shard."""
    b, s, d = x.shape
    mesh = active_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1:
        dp_axes = tuple(a for a in mesh.axis_names if a != "model")
        n_dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes \
            else 1
        tp = mesh.shape["model"]
        if (b % n_dp == 0 and s % tp == 0
                and mcfg.n_experts % tp == 0):
            axes = tuple(mesh.axis_names)
            bspec = dp_axes if len(dp_axes) > 1 else (
                dp_axes[0] if dp_axes else None)
            # FSDP the expert hidden dims over every DP axis when divisible
            fsdp = (dp_axes if dp_axes and d % n_dp == 0
                    and mcfg.expert_ff % n_dp == 0 else ())
            wspec = P("model", fsdp if fsdp else None, None)
            fn = jax.shard_map(
                functools.partial(_moe_shard, mcfg=mcfg, ep_axis="model",
                                  all_axes=axes, fsdp_axis=fsdp),
                mesh=mesh,
                in_specs=(P(bspec, "model", None), P(None, None),
                          wspec, wspec, wspec),
                out_specs=(P(bspec, "model", None), P()),
                check_vma=False)
            return fn(x, p["wr"], p["wg"], p["wu"], p["wd"])
    y, aux = _moe_local(x.reshape(b * s, d), p, mcfg)
    return y.reshape(b, s, d), aux
