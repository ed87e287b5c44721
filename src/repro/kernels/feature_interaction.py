"""Batched pairwise-dot feature interaction — paper Fig. 3 / Fig. 11.

Computes Z = X X^T per sample on the MXU (the batched-GEMM the paper's
feature-interaction unit runs on four FP_MATRIX_MULT PEs). The
lower-triangle extraction is done outside the kernel in ops.py (cheap,
bandwidth-trivial); the kernel owns the compute-heavy GEMM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm import f32_precision


def _interact_kernel(x_ref, o_ref):
    x = x_ref[...]
    o_ref[...] = jax.lax.dot_general(
        x, x,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=f32_precision(x.dtype),
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def interaction(x: jax.Array, *, bb: int = 64,
                interpret: bool = False) -> jax.Array:
    """x: (B, F, D) -> (B, F, F) pairwise dots per sample."""
    b, f, d = x.shape
    bb = min(bb, b)
    grid = (pl.cdiv(b, bb),)
    return pl.pallas_call(
        _interact_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bb, f, d), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, f, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
