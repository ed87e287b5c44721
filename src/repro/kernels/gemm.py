"""Output-stationary blocked matmul — the Centaur *dense accelerator*.

TPU adaptation of the paper's 4x4 PE array of 32x32 FP_MATRIX_MULT blocks
(Fig. 11/12): the output-stationary dataflow survives — an fp32 accumulator
tile stays resident in VMEM while weight/input tiles stream through the MXU —
but the tile size is re-chosen for TPU hardware (128-aligned MXU tiles,
VMEM-sized working set) instead of the FPGA's 32x32 DSP granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def f32_precision(dtype):
    """Full f32 products for f32 operands. Mosaic's default contracts f32
    in one bf16 pass, which moves a served CTR by ~1e-2 against the f32
    model (measured on a v5e)."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU matmul on the current (bm, bk) x (bk, bn) tile pair; partial sums
    # accumulate output-stationary in VMEM scratch (the per-PE SRAM analogue).
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=f32_precision(x_ref.dtype))

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def gemm(x: jax.Array, w: jax.Array, *, bm: int = 128, bn: int = 128,
         bk: int = 128, interpret: bool = False) -> jax.Array:
    """x:(M,K) @ w:(K,N) -> (M,N) in x.dtype with fp32 accumulation."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn = min(bm, m), min(bn, n)
    # K is the contraction dim: a ragged tail block would feed undefined
    # values into the accumulator, so zero-pad K to a multiple of bk —
    # zero products add nothing. (Ragged tails along M/N only touch
    # discarded output rows/cols — safe.)
    bk = min(bk, k)
    if k % bk:
        x = jnp.pad(x, ((0, 0), (0, bk - k % bk)))
        w = jnp.pad(w, ((0, bk - k % bk), (0, 0)))
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
