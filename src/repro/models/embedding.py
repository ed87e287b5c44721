"""Token embedding + LM head over a row-sharded vocab table.

This is the Centaur sparse engine applied to LMs: the vocab table (up to
256 k rows here) is the "embedding table in CPU DIMMs"; rows are sharded
across the 'model' axis and each chip gathers the rows it owns (masked
local gather -> psum), so only (tokens x d_model) activations ever cross
chips — never table rows. The LM head needs no gather at all: the matmul
against the row-sharded table contracts d_model locally and leaves logits
vocab-sharded.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import active_mesh, batch_axes, constrain
from repro.models.params import Builder

VOCAB_PAD = 128


def padded_vocab(v: int) -> int:
    return ((v + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


def init_table(b: Builder, vocab: int, d: int):
    vpad = padded_vocab(vocab)
    p = b.normal((vpad, d), ("model", None), scale=0.02)
    # zero the padding rows so tied logits for pad ids stay inert
    p.value = p.value.at[vocab:].set(0)
    return p


def _local_gather(table_shard, tokens, axis: str):
    """Masked local gather + psum — EB-Streamer over the pod HBM pool."""
    my = jax.lax.axis_index(axis)
    vloc = table_shard.shape[0]
    lo = my * vloc
    rel = tokens - lo
    ok = (rel >= 0) & (rel < vloc)
    rows = jnp.take(table_shard, jnp.where(ok, rel, 0), axis=0)
    rows = jnp.where(ok[..., None], rows, 0)
    return jax.lax.psum(rows, axis)


def embed_tokens(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """tokens (B, S) -> (B, S, D)."""
    mesh = active_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1:
        ba = batch_axes(mesh)
        n_batch_shards = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
        if tokens.shape[0] % n_batch_shards == 0:
            bspec = ba if len(ba) > 1 else (ba[0] if ba else None)
            fn = jax.shard_map(
                lambda t, tok: _local_gather(t, tok, "model"),
                mesh=mesh,
                in_specs=(P("model", None), P(bspec, None)),
                out_specs=P(bspec, None, None), check_vma=False)
            return fn(table, tokens)
    # Fallback (no mesh / tiny batch): direct gather; GSPMD partitions it.
    return jnp.take(table, tokens, axis=0)


def lm_head(x: jax.Array, table: jax.Array, vocab: int) -> jax.Array:
    """x (B, S, D) @ table.T -> vocab-sharded logits with pads masked."""
    logits = jnp.einsum("bsd,vd->bsv", x, table,
                        preferred_element_type=jnp.float32)
    logits = constrain(logits, "batch", None, "model")
    vpad = table.shape[0]
    if vpad != vocab:
        mask = (jnp.arange(vpad) < vocab)
        logits = jnp.where(mask, logits, -1e30)
    return logits


def init_unembed(b: Builder, vocab: int, d: int):
    vpad = padded_vocab(vocab)
    return b.normal((d, vpad), (None, "model"), scale=0.02)


def lm_head_untied(x: jax.Array, w: jax.Array, vocab: int) -> jax.Array:
    logits = jnp.einsum("bsd,dv->bsv", x, w,
                        preferred_element_type=jnp.float32)
    logits = constrain(logits, "batch", None, "model")
    vpad = w.shape[1]
    if vpad != vocab:
        mask = (jnp.arange(vpad) < vocab)
        logits = jnp.where(mask, logits, -1e30)
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """Mean masked next-token CE. logits (B,S,V) f32, labels (B,S) int.

    Written gather-free along the vocab axis: a take_along_axis over the
    sharded V dim makes GSPMD all-gather the logits (12+ GB/device at 4k x
    49k); the one-hot masked sum below reduces over the sharded dim locally
    and only all-reduces (B, S) scalars.
    """
    logits = constrain(logits, "batch", None, "model")
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jnp.arange(logits.shape[-1])
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
