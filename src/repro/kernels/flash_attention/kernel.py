"""Flash attention Pallas TPU kernel (online softmax, VMEM-tiled).

TPU adaptation (DESIGN.md §2): instead of the CUDA warp-level algorithm,
tiles are sized to the MXU (128x128) and staged HBM->VMEM via BlockSpecs;
the online-softmax state (m, l, acc) lives in VMEM scratch across the
innermost (arbitrary-order) K-block grid dimension.  GQA is expressed in
the K/V BlockSpec index maps (q-head b maps to kv-head b // group), so
grouped KV is never materialized.

Grid: (batch*q_heads, q_blocks, k_blocks); k innermost.
The VMEM working set per step is q(bq*d) + k(bk*d) + v(bk*d) + acc(bq*d)
f32 + scratch — with bq=bk=128, d<=256 this is < 1 MiB, far under VMEM;
larger bq amortizes the q load (see EXPERIMENTS §Perf).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv

NEG_INF = -1e30  # avoid NaNs from (-inf) - (-inf) in fully-masked rows


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, seq_q: int, seq_k: int,
                  num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)      # [bq, d]
    k = k_ref[0].astype(jnp.float32)      # [bk, d]
    v = v_ref[0].astype(jnp.float32)      # [bk, d]
    # zero the seq-padding rows of v: p is 0 there, but 0 * garbage = NaN
    kvalid = (ki * block_k +
              jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
              ) < seq_k
    v = jnp.where(kvalid, v, 0.0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]

    # positional mask: causal / sliding window / tail padding
    qpos = (qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            + (seq_k - seq_q))            # right-aligned
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k
    if causal or window is not None:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None] +
                    jax.lax.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jnp.ndarray,   # [BH, Sq, D]  (batch*q_heads flattened)
    k: jnp.ndarray,   # [BKV, Sk, D] (batch*kv_heads flattened)
    v: jnp.ndarray,
    *,
    group: int,                      # q heads per kv head
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    assert bh == bkv * group, (bh, bkv, group)
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = cdiv(sq, block_q)
    nk = cdiv(sk, block_k)
    grid = (bh, nq, nk)

    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_q=sq, seq_k=sk,
        num_k_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
