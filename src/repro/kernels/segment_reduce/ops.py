"""Strategy-dispatched segment reduce: tiled Pallas kernel, fused/sorted
jnp paths, and a scatter reference, tuned per shape.

``segment_reduce`` is the keyed-aggregation primitive behind
``MaRe.reduce_by_key``: both the map-side combiner (pre-shuffle) and the
post-shuffle merge fold records into a bounded ``[num_keys, ...]`` key
table.  Four strategies implement the same contract (see
:func:`segment_reduce_ref` for semantics, docs/kernels.md for the why):

=========  ========================================  ==================
strategy   implementation                            availability
=========  ========================================  ==================
scatter    per-leaf ``.at[].add/.max/.min``          all monoids/dtypes
fused      dtype-grouped single-scatter sum          sum only
sorted     argsort + cumsum + boundary diff          sum, int leaves
tiled      Pallas kernel, VMEM-tiled key table       sum only
=========  ========================================  ==================

Dispatch (``use_kernel`` tri-state, back-compat with the pre-tiling API):

* ``use_kernel=True``  — force the Pallas ``tiled`` kernel.
* ``use_kernel=False`` — force the plain ``scatter`` reference (the
  bench's fallback baseline).
* ``use_kernel=None``  (the default) — ``REPRO_SEGMENT_KERNEL=1/0`` still
  forces tiled/scatter; otherwise the autotuner in ``tune.py`` measures
  the candidates at first trace for this shape and the winner is cached
  per (backend, op, n, num_keys, leaf signature).  This is the flipped
  default gated by ``kernel_vs_fallback_warm >= 1.0`` in
  ``benchmarks/kmer.py``.

Degenerate shapes short-circuit to ``scatter`` regardless: an empty
shard (``n == 0``) would give the tiled kernel a zero-length grid (its
outputs would never be written), and an empty value pytree has no leaf
to carry the kernel's count table.  Non-``sum`` monoids are scatter-only.

Overflow contract (all strategies): valid records whose key falls
outside ``[0, num_keys)`` contribute to ``result.overflow`` and nothing
else — the planner turns a nonzero count into an action-time error
instead of silently corrupting table rows.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import use_interpret
from repro.kernels.segment_reduce.kernel import (DEFAULT_BLOCK,
                                                DEFAULT_KEY_BLOCK,
                                                segment_sum_tiled, tiling)
from repro.kernels.segment_reduce.ref import (MONOIDS, SegmentReduceResult,
                                              monoid_identity,
                                              segment_reduce_fused,
                                              segment_reduce_ref,
                                              segment_reduce_sorted)
from repro.kernels.segment_reduce.tune import pick_strategy

STRATEGIES = ("scatter", "fused", "sorted", "tiled")


def resolve_use_kernel(explicit: Optional[bool], op: str) -> bool:
    """Back-compat predicate: would the *Pallas kernel* run?  (The full
    dispatch is :func:`resolve_strategy`; this answers only the
    tiled-vs-not question the original tri-state API exposed.)"""
    if op != "sum":
        return False
    if explicit is not None:
        return explicit
    env = os.environ.get("REPRO_SEGMENT_KERNEL")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() == "tpu"


def resolve_strategy(use_kernel: Optional[bool], op: str, n: int,
                     num_keys: int, values: Any,
                     strategy: Optional[str] = None):
    """Map the public knobs to ``(strategy, block, key_block)``.

    ``strategy`` (when given) wins outright; otherwise ``use_kernel``
    True/False force tiled/scatter, ``REPRO_SEGMENT_KERNEL`` forces next,
    and the remaining ``None`` case asks the autotuner.  Returned block
    sizes are 0 for non-tiled strategies (callers' explicit ``block`` /
    ``key_block`` still override).
    """
    leaves = jax.tree.leaves(values)
    if op != "sum" or not leaves or n == 0:
        return ("scatter", 0, 0)
    if strategy is not None:
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown segment-reduce strategy {strategy!r};"
                             f" expected one of {STRATEGIES}")
        return (strategy, 0, 0)
    if use_kernel is True:
        return ("tiled", 0, 0)
    if use_kernel is False:
        return ("scatter", 0, 0)
    env = os.environ.get("REPRO_SEGMENT_KERNEL")
    if env is not None:
        return (("scatter", 0, 0) if env in ("0", "false", "False")
                else ("tiled", 0, 0))
    return pick_strategy(op, n, num_keys, values)


@functools.partial(jax.jit, static_argnames=("num_keys", "op", "strategy",
                                             "block", "key_block",
                                             "interpret"))
def segment_reduce_impl(keys: jnp.ndarray, values: Any, num_keys: int,
                        op: str, valid: jnp.ndarray, strategy: str,
                        block: int, key_block: int,
                        interpret: bool) -> SegmentReduceResult:
    """jit'd single-strategy implementation (``strategy`` is static — the
    autotuner times each candidate through this exact entry point)."""
    if strategy == "fused":
        return segment_reduce_fused(keys, values, num_keys, valid=valid)
    if strategy == "sorted":
        return segment_reduce_sorted(keys, values, num_keys, valid=valid)
    if strategy == "tiled":
        return segment_sum_tiled(keys, values, num_keys, valid, block=block,
                                 key_block=key_block, interpret=interpret)
    return segment_reduce_ref(keys, values, num_keys, op=op, valid=valid)


def segment_reduce(keys: jnp.ndarray, values: Any, num_keys: int,
                   op: str = "sum",
                   valid: Optional[jnp.ndarray] = None,
                   use_kernel: Optional[bool] = None,
                   strategy: Optional[str] = None,
                   block: Optional[int] = None,
                   key_block: Optional[int] = None,
                   interpret: Optional[bool] = None) -> SegmentReduceResult:
    """Aggregate ``values`` ([n, ...] pytree) per key into a
    ``[num_keys, ...]`` table.

    Args:
      keys: int ``[n]`` key per record; out-of-range keys count into
        ``result.overflow`` and touch no table row.
      values: pytree of ``[n, ...]`` arrays (may be empty — counts only).
      num_keys: static key-space bound; the table has exactly this many
        rows, absent keys hold the monoid identity (``counts > 0`` marks
        presence).
      op: monoid, one of ``("sum", "max", "min")``.
      valid: bool ``[n]`` record mask (``Partition.mask()``); ``None``
        means all valid.
      use_kernel: tri-state dispatch — True forces the Pallas tiled
        kernel, False forces the scatter reference, None (default)
        autotunes (see module docstring for the env overrides).
      strategy: explicit strategy name overriding ``use_kernel``
        entirely; one of ``STRATEGIES``.
      block: record-block length for the tiled kernel grid; ``None``
        takes the tuned or default tiling.
      key_block: key-table tile height for the tiled kernel; ``None``
        takes the tuned or default tiling.
      interpret: force/forbid Pallas interpret mode; ``None`` follows
        :func:`use_interpret` (interpret everywhere but real TPU).

    Returns a :class:`SegmentReduceResult` ``(values, counts, overflow)``;
    all strategies are exact (bit-identical for int dtypes) — see
    ``tests/test_kernels_segment.py``.
    """
    n = keys.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    strat, tuned_block, tuned_kb = resolve_strategy(
        use_kernel, op, n, num_keys, values, strategy=strategy)
    if strat == "tiled":
        block, kb = tiling(n, num_keys, block or tuned_block or DEFAULT_BLOCK,
                           key_block or tuned_kb or DEFAULT_KEY_BLOCK)
    else:
        kb = 0
        block = 0
    interp = use_interpret() if interpret is None else interpret
    return segment_reduce_impl(keys, values, num_keys, op=op, valid=valid,
                               strategy=strat, block=block, key_block=kb,
                               interpret=interp)


__all__ = ["segment_reduce", "segment_reduce_impl", "segment_reduce_ref",
           "segment_reduce_fused", "segment_reduce_sorted",
           "resolve_use_kernel", "resolve_strategy", "STRATEGIES",
           "SegmentReduceResult", "MONOIDS", "monoid_identity",
           "segment_sum_tiled"]
