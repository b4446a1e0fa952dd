"""Fused RMSNorm Pallas kernel (row-blocked, VPU).

Norm layers are memory-bound (AI ~ O(1)); fusing square/mean/rsqrt/scale
into one VMEM pass removes two HBM round-trips vs. the unfused graph.
Rows are tiled [block_rows, d]; the weight vector is broadcast into VMEM
once per block (index_map pins it to block 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, d: int):
    x = x_ref[...].astype(jnp.float32)          # [block_rows, d]
    w = w_ref[...].astype(jnp.float32)          # [d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w[None, :]).astype(o_ref.dtype)


def rmsnorm_kernel(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
                   block_rows: int = 256, interpret: bool = True
                   ) -> jnp.ndarray:
    """x: [rows, d], weight: [d] -> [rows, d]."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    nb = cdiv(rows, block_rows)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps, d=d)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda b: (b, 0)),
            pl.BlockSpec((d,), lambda b: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, weight)
