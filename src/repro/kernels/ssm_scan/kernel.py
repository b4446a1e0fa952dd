"""Fused selective-scan Pallas kernel (the SSM/hybrid hot-spot).

The XLA fallback (models/ssm.py) is memory-bound: `associative_scan`
materializes O(log T) full [T, d, n] levels in HBM (~55% of hymba's
train traffic — EXPERIMENTS.md §Perf hymba-stop).  This kernel computes
the selective-SSM coefficients AND the recurrence inside VMEM: the only
HBM traffic is x in ([chunk, d]) and y out ([chunk, d]) — O(T·d) instead
of O(T·d·n·log T).

Grid: (batch, num_chunks); the chunk axis is sequential ("arbitrary")
with the [d, n] recurrent state carried in VMEM scratch.  Within a chunk
the recurrence runs as a fori_loop of VPU ops on the [d, n] tile
(d=1600, n=16 → 100 KiB f32 state; coefficient tiles a/bx are
[chunk, d, n] ≈ 26 MiB at chunk=256 — comfortably inside VMEM).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _ssm_kernel(xc_ref, xproj_ref, dtb_ref, alog_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, chunk: int, seq: int,
                num_chunks: int, n: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    xc = xc_ref[0].astype(jnp.float32)               # [chunk, d]
    xproj = xproj_ref[...].astype(jnp.float32)       # [d, 2n+1]
    proj = jnp.dot(xc, xproj,
                   preferred_element_type=jnp.float32)   # [chunk, 2n+1]
    bb = proj[:, :n]                                 # [chunk, n]
    cc = proj[:, n:2 * n]
    dt = jax.nn.softplus(proj[:, 2 * n][:, None] + dtb_ref[...][None, :])
    a = jnp.exp(-jnp.exp(alog_ref[...])[None] * dt[..., None])
    bx = (dt * xc)[..., None] * bb[:, None, :]       # [chunk, d, n]
    # mask padded tail: identity update
    tpos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk,), 0)
    valid = (tpos < seq)[:, None, None]
    a = jnp.where(valid, a, 1.0)
    bx = jnp.where(valid, bx, 0.0)

    def body(t, carry):
        h, ys = carry
        h = a[t] * h + bx[t]                         # [d, n]
        y_t = jnp.sum(h * cc[t][None, :], axis=-1)   # [d]
        sel = (jax.lax.broadcasted_iota(jnp.int32, ys.shape, 0) == t)
        ys = jnp.where(sel, y_t[None, :], ys)
        return h, ys

    h0 = h_ref[...]
    ys0 = jnp.zeros((chunk, xc.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, body, (h0, ys0))
    h_ref[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _done():
        hout_ref[0] = h_ref[...]


def ssm_scan_kernel(xc: jnp.ndarray, x_proj: jnp.ndarray,
                    dt_bias: jnp.ndarray, a_log: jnp.ndarray,
                    h0: jnp.ndarray, chunk: int = 128,
                    interpret: bool = True
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xc: [B, T, d] -> (y [B, T, d] f32, h_final [B, d, n] f32)."""
    b, t, d = xc.shape
    n = a_log.shape[1]
    chunk = min(chunk, t)
    nc = cdiv(t, chunk)
    kernel = functools.partial(_ssm_kernel, chunk=chunk, seq=t,
                               num_chunks=nc, n=n)
    return pl.pallas_call(
        kernel,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((d, 2 * n + 1), lambda bi, ci: (0, 0)),
            pl.BlockSpec((d,), lambda bi, ci: (0,)),
            pl.BlockSpec((d, n), lambda bi, ci: (0, 0)),
            pl.BlockSpec((1, d, n), lambda bi, ci: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, d, n), lambda bi, ci: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc * chunk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xc, x_proj, dt_bias, a_log, h0)
