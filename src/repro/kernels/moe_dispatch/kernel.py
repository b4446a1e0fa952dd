"""MoE dispatch-slotting Pallas kernel — repartitionBy's pack hot-spot.

Computes, for each token, its slot position within its destination group
(expert / shard) plus per-group counts, in one streaming pass.  This is the
integer prelude to the all_to_all in both MoE expert dispatch and MaRe's
generic repartitionBy (DESIGN.md §3.2).

TPU mapping: gathers (`counts[assign_i]`) are rewritten as one-hot matmuls
so the whole kernel is VPU/MXU reductions over a [block, groups] one-hot
tile; running per-group counts persist in VMEM scratch across the
(arbitrary) block grid.  Working set: block x groups i32 — 256 x 512 = 512
KiB, well under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _dispatch_kernel(assign_ref, pos_ref, counts_out_ref, counts_ref, *,
                     num_groups: int, block: int, n: int, num_blocks: int):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    a = assign_ref[...]                                   # [block] int32
    idx = bi * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
    valid = idx < n
    a = jnp.where(valid, a, num_groups)                   # padding sentinel
    gid = jax.lax.broadcasted_iota(jnp.int32, (block, num_groups), 1)
    onehot = (a[:, None] == gid).astype(jnp.int32)        # [block, G]
    within = jnp.cumsum(onehot, axis=0) - onehot
    base = jnp.sum(onehot * counts_ref[...][None, :], axis=1)
    pos_ref[...] = base + jnp.sum(within * onehot, axis=1)
    counts_ref[...] = counts_ref[...] + jnp.sum(onehot, axis=0)

    @pl.when(bi == num_blocks - 1)
    def _finalize():
        counts_out_ref[...] = counts_ref[...]


def moe_dispatch_kernel(assignments: jnp.ndarray, num_groups: int,
                        block: int = 256, interpret: bool = True):
    """assignments: [n] int32 -> (positions [n], counts [num_groups])."""
    n = assignments.shape[0]
    block = min(block, n)
    nb = cdiv(n, block)
    kernel = functools.partial(_dispatch_kernel, num_groups=num_groups,
                               block=block, n=n, num_blocks=nb)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda b: (b,))],
        out_specs=[
            pl.BlockSpec((block,), lambda b: (b,)),
            pl.BlockSpec((num_groups,), lambda b: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((num_groups,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((num_groups,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(assignments.astype(jnp.int32))
