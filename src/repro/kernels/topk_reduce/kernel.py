"""Streaming top-k Pallas kernel — the MaRe ``reduce`` combiner hot-spot.

The Virtual-Screening pipeline (paper Listing 2) reduces millions of scored
records to the best 30 via sdsorter.  On TPU, the combiner becomes a
single-pass streaming selection: score blocks are staged HBM->VMEM; a
running top-k buffer lives in VMEM scratch across the (arbitrary-order)
block grid dimension; each step merges the block into the buffer with k
iterative max-extractions (VPU-friendly: max/argmax reductions + select —
no data-dependent gathers, no sort network needed for k << block).

VMEM working set: block (f32) + k-buffers — block=1024, k<=64 is ~8 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv

NEG_INF = -1e30


def _topk_kernel(scores_ref, count_ref, out_val_ref, out_idx_ref,
                 best_v_ref, best_i_ref, *, k: int, block: int, n: int,
                 num_blocks: int):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        best_v_ref[...] = jnp.full_like(best_v_ref, NEG_INF)
        best_i_ref[...] = jnp.full_like(best_i_ref, -1)

    s = scores_ref[...].astype(jnp.float32)              # [block]
    idx = bi * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
    valid = (idx < n) & (idx < count_ref[0])
    s = jnp.where(valid, s, NEG_INF)

    # merge candidates = running buffer ++ block
    cand_v = jnp.concatenate([best_v_ref[...], s])
    cand_i = jnp.concatenate([best_i_ref[...], idx])

    def select_one(j, carry):
        cv, ci, bv, bi_ = carry
        m = jnp.max(cv)
        am = jnp.argmax(cv)
        sel = jax.lax.broadcasted_iota(jnp.int32, cv.shape, 0) == am
        mi = jnp.sum(jnp.where(sel, ci, 0))
        bv = jnp.where(jax.lax.broadcasted_iota(jnp.int32, bv.shape, 0) == j,
                       m, bv)
        bi_ = jnp.where(jax.lax.broadcasted_iota(jnp.int32, bi_.shape, 0) == j,
                        mi, bi_)
        cv = jnp.where(sel, NEG_INF, cv)
        return cv, ci, bv, bi_

    _, _, new_v, new_i = jax.lax.fori_loop(
        0, k, select_one,
        (cand_v, cand_i, jnp.zeros((k,), jnp.float32),
         jnp.zeros((k,), jnp.int32)))
    best_v_ref[...] = new_v
    best_i_ref[...] = new_i

    @pl.when(bi == num_blocks - 1)
    def _finalize():
        out_val_ref[...] = best_v_ref[...]
        out_idx_ref[...] = best_i_ref[...]


def topk_reduce_kernel(scores: jnp.ndarray, k: int,
                       valid_count: jnp.ndarray,
                       block: int = 1024,
                       interpret: bool = True):
    """scores: [n] -> (values [k] desc, indices [k])."""
    n = scores.shape[0]
    block = min(block, max(8, n))
    nb = cdiv(n, block)
    kernel = functools.partial(_topk_kernel, k=k, block=block, n=n,
                               num_blocks=nb)
    count = jnp.asarray(valid_count, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda b: (b,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((k,), lambda b: (0,)),
            pl.BlockSpec((k,), lambda b: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k,), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((k,), jnp.float32),
            pltpu.VMEM((k,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scores, count)
