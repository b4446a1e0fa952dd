"""Tiled segment-sum Pallas kernel — the ``reduce_by_key`` combiner hot-spot.

Sort-free scatter-accumulate over a bounded key table, tiled on both
axes.  The grid is ``(key_tiles, record_blocks)`` with the key axis
outermost: for key tile ``kt`` only a ``[m, key_block]`` slice of the
(transposed) aggregate table is resident in VMEM scratch, and the
sequential inner axis streams ``block`` records HBM->VMEM and accumulates
into that tile.  Scatter is re-expressed as MXU matmuls against a
tile-local one-hot, the same no-data-dependent-gather discipline as the
top-k kernel.

Layout (what Mosaic accepts on a v5e, checked by
``tests/test_chip_compile.py``):

* Records are **lane-major**.  Keys arrive as a dense ``[n / 128, 128]``
  int32 array and value columns as ``[cols, n / 128, 128]``, so HBM holds
  4 bytes per record and column, and each 128-record group is one
  ``(1, 128)`` row.  Per group the one-hot is ``(key_id[:, None] ==
  keys_row)`` of shape ``[key_block, 128]`` — a sublane broadcast, with
  no 1-D -> 2-D reshape (Mosaic refuses ``vector<Nxi1> -> vector<Nx1xi1>``).
* The per-group contraction is ``lhs [m, 128] . one_hot^T`` with the
  value columns stacked on sublanes of ``lhs``, so every column (and the
  record count) shares one MXU pass.
* **Integer columns are summed exactly without an integer matmul** (the
  MXU path refuses ``i32`` operands).  Each int32 column is split into
  its four bytes, each byte a bf16 row of ``lhs``; a byte sum over one
  record block is at most ``255 * block < 2**24``, so the f32 product is
  exact, converted to int32 and accumulated per byte.  The wrapper
  recombines ``sum_j byte_sum_j << 8j`` in wrapping int32 arithmetic,
  which equals the wrapping scatter-add bit for bit.  The count is one
  more row of ones.  Floating columns take an f32 pass (``HIGHEST``
  precision) with an f32 table, as exact as the blockwise f32 sum.
* **Block-range early-out.**  A record block whose key range misses the
  resident tile skips its matmuls (``pl.when`` on the block's key
  min/max).  Key-sorted input (the post-shuffle merge of compacted
  tables) overlaps ~1 tile per block; unsorted input runs dense, which
  is ``n * num_keys`` one-hot cells (the autotuner's bound, ``tune.py``).

Validity is folded in before the kernel: invalid records and keys outside
``[0, num_keys)`` get key ``-1``, which matches no tile, and their
overflow is counted in jnp.  Padding up to a whole block uses the same
sentinel and zero values.  Sum only — max/min take the jnp path (ops.py).
"""
from __future__ import annotations

import functools
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, SUBLANE, cdiv, round_up
from repro.kernels.segment_reduce.ref import SegmentReduceResult

#: Records per block are a whole number of (8, 128) int32 tiles.
BLOCK_ALIGN = SUBLANE * LANES
#: Largest block: the kernel unrolls one matmul per 128 records, and a
#: block's byte sums must stay exact in f32 (255 * block < 2**24).
MAX_BLOCK = 8192
#: Default tiling when the caller forces the kernel without one.
DEFAULT_BLOCK = 2048
DEFAULT_KEY_BLOCK = 4096


def _kernel(keys_ref, vals_ref, out_ref, acc_ref, *, rows: int,
            key_block: int, ncols: int, exact_int: bool):
    kt = pl.program_id(0)          # key tile (outer; owns the output tile)
    bi = pl.program_id(1)          # record block (inner, sequential)
    tile_lo = kt * key_block
    m = acc_ref.shape[0]

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keys = keys_ref[...]                                  # [rows, 128]
    kmax = jnp.max(keys)
    kmin = jnp.min(jnp.where(keys >= 0, keys, jnp.int32(2 ** 31 - 1)))

    @pl.when((kmin < tile_lo + key_block) & (kmax >= tile_lo))
    def _accumulate():
        kid = tile_lo + jax.lax.broadcasted_iota(
            jnp.int32, (key_block, LANES), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (m, LANES), 0)
        total = None
        for r in range(rows):                             # 128-record groups
            one_hot = jnp.where(kid == keys_ref[r:r + 1, :], 1.0, 0.0)
            if exact_int:
                # rows 4c..4c+3: bytes of column c; row 4*ncols: ones
                lhs = jnp.where(row == 4 * ncols, 1, 0)
                for c in range(ncols):
                    byte = row - 4 * c
                    mine = (byte >= 0) & (byte < 4)
                    shifted = jax.lax.shift_right_logical(
                        vals_ref[c, r:r + 1, :],
                        jnp.where(mine, byte * 8, 0))
                    lhs = jnp.where(mine, shifted & 255, lhs)
                lhs = lhs.astype(jnp.float32).astype(jnp.bfloat16)
                one_hot = one_hot.astype(jnp.bfloat16)
                precision = None
            else:
                lhs = jnp.zeros((m, LANES), jnp.float32)
                for c in range(ncols):
                    lhs = jnp.where(row == c, vals_ref[c, r:r + 1, :], lhs)
                precision = jax.lax.Precision.HIGHEST
            part = jax.lax.dot_general(
                lhs, one_hot, (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32)       # [m, key_block]
            total = part if total is None else total + part
        acc_ref[...] += total.astype(acc_ref.dtype)

    @pl.when(bi == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[...] = acc_ref[...]


def _table_pass(keys2: jax.Array, cols: jax.Array, ncols: int,
                num_keys: int, block: int, key_block: int, exact_int: bool,
                interpret: bool) -> jax.Array:
    """One pallas_call: ``[m, num_keys]`` table of per-key sums of the
    first ``ncols`` rows of ``cols`` (byte sums + count row when
    ``exact_int``)."""
    m = round_up(4 * ncols + 1 if exact_int else ncols, SUBLANE)
    rows = block // LANES
    acc_dtype = jnp.int32 if exact_int else jnp.float32
    kernel = functools.partial(_kernel, rows=rows, key_block=key_block,
                               ncols=ncols, exact_int=exact_int)
    return pl.pallas_call(
        kernel,
        grid=(cdiv(num_keys, key_block), keys2.shape[0] // rows),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda k, b: (b, 0)),
            pl.BlockSpec((cols.shape[0], rows, LANES),
                         lambda k, b: (0, b, 0)),
        ],
        out_specs=pl.BlockSpec((m, key_block), lambda k, b: (0, k)),
        out_shape=jax.ShapeDtypeStruct((m, num_keys), acc_dtype),
        scratch_shapes=[pltpu.VMEM((m, key_block), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="segment_sum_tiled",
    )(keys2, cols)


def tiling(n: int, num_keys: int, block: int, key_block: int
           ) -> Tuple[int, int]:
    """The ``(block, key_block)`` the kernel really runs for a request:
    ``block`` rounded up to whole (8, 128) tiles and to no more than the
    padded record count; ``key_block`` a multiple of 128 or the whole
    table."""
    block = round_up(max(block, 1), BLOCK_ALIGN)
    block = min(block, round_up(max(n, 1), BLOCK_ALIGN), MAX_BLOCK)
    key_block = (num_keys if key_block >= num_keys
                 else min(round_up(key_block, LANES), num_keys))
    return block, key_block


def vmem_bytes(block: int, key_block: int, ncols: int = 1) -> int:
    """Scoped VMEM one grid step needs: the ``[key_block, 128]`` one-hot
    and its compare/convert temporaries (5 bytes per cell, which matches
    what the v5e compiler reports), the double-buffered accumulator
    output plus its scratch, and the double-buffered record blocks."""
    m = round_up(4 * ncols + 1, SUBLANE)
    return (5 * key_block * LANES + 3 * m * key_block * 4
            + 2 * (1 + ncols) * block * 4)


def _lane_major(cols: List[jax.Array], n_pad: int, dtype) -> jax.Array:
    """Stack ``[n]`` columns into the kernel's ``[cols, n_pad/128, 128]``
    (one zero column when there are none: the kernel needs an operand)."""
    if not cols:
        return jnp.zeros((1, n_pad // LANES, LANES), dtype)
    mat = jnp.stack([c.astype(dtype) for c in cols])
    mat = jnp.pad(mat, ((0, 0), (0, n_pad - mat.shape[1])))
    return mat.reshape(len(cols), n_pad // LANES, LANES)


def segment_sum_tiled(keys: jax.Array, values: Any, num_keys: int,
                      valid: jax.Array, block: int = DEFAULT_BLOCK,
                      key_block: int = DEFAULT_KEY_BLOCK,
                      interpret: bool = True) -> SegmentReduceResult:
    """Tiled Pallas segment sum of a value pytree.

    ``keys`` [n] int, ``values`` pytree of ``[n, ...]`` leaves, ``valid``
    [n] bool -> :class:`SegmentReduceResult` with ``[num_keys, ...]``
    tables, ``[num_keys]`` int32 counts and the int32 overflow scalar.
    Integer leaves are exact (wrapping, like the scatter oracle);
    floating leaves sum in f32.  ``block`` / ``key_block`` are tuning
    knobs normalized by :func:`tiling`; results never depend on them.
    """
    n = keys.shape[0]
    block, key_block = tiling(n, num_keys, block, key_block)
    n_pad = round_up(max(n, 1), block)
    keys = keys.astype(jnp.int32)
    in_range = (keys >= 0) & (keys < num_keys)
    ok = valid & in_range
    overflow = jnp.sum(valid & ~in_range).astype(jnp.int32)
    keys2 = jnp.pad(jnp.where(ok, keys, -1), (0, n_pad - n),
                    constant_values=-1).reshape(n_pad // LANES, LANES)

    leaves, treedef = jax.tree.flatten(values)
    flat = [leaf.reshape(n, -1) for leaf in leaves]
    is_float = [jnp.issubdtype(leaf.dtype, jnp.floating) for leaf in leaves]
    int_cols = [f[:, j] for f, fl in zip(flat, is_float) if not fl
                for j in range(f.shape[1])]
    # a masked float slot may hold NaN/inf, and 0 * NaN poisons the sum
    float_cols = [jnp.where(ok, f[:, j], 0) for f, fl in zip(flat, is_float)
                  if fl for j in range(f.shape[1])]

    # the integer pass always runs: it carries the count row
    nint = len(int_cols)
    acc = _table_pass(keys2, _lane_major(int_cols, n_pad, jnp.int32), nint,
                      num_keys, block, key_block, True, interpret)
    counts = acc[4 * nint]
    int_sums = [acc[4 * c] + (acc[4 * c + 1] << 8) + (acc[4 * c + 2] << 16)
                + (acc[4 * c + 3] << 24) for c in range(nint)]
    float_sums: List[jax.Array] = []
    if float_cols:
        facc = _table_pass(keys2, _lane_major(float_cols, n_pad, jnp.float32),
                           len(float_cols), num_keys, block, key_block, False,
                           interpret)
        float_sums = [facc[c] for c in range(len(float_cols))]

    tables = []
    int_it, float_it = iter(int_sums), iter(float_sums)
    for leaf, f, fl in zip(leaves, flat, is_float):
        it = float_it if fl else int_it
        cols = [next(it) for _ in range(f.shape[1])]
        tab = jnp.stack(cols, axis=1) if cols else jnp.zeros(
            (num_keys, 0), leaf.dtype)
        tables.append(tab.astype(leaf.dtype).reshape(
            (num_keys,) + leaf.shape[1:]))
    return SegmentReduceResult(values=jax.tree.unflatten(treedef, tables),
                               counts=counts, overflow=overflow)
