"""First-compile autotuner for segment reduce.

``segment_reduce(..., use_kernel=None)`` doesn't hardcode a strategy: the
first time a given problem shape is traced, :func:`pick_strategy` runs
every eligible implementation on synthetic data of that exact shape,
times a few warm repetitions each, and caches the winner per

    (backend, op, n, num_keys, leaf-signature)

where the leaf signature is the tuple of ``(trailing shape, dtype)`` per
value leaf.  Tuning happens *at trace time* — candidate impls are jit'd
and executed on concrete arrays under ``jax.ensure_compile_time_eval()``,
which runs them eagerly instead of staging them into the caller's trace.  The cost
grows with the shape (compile plus four runs per candidate), is paid
once per process and shape, and is amortized by the plan cache (a cached
compiled program never re-traces, so it never re-tunes).

Candidate set (see docs/kernels.md for the measured numbers):

* ``scatter`` — :func:`segment_reduce_ref`, one ``.at[].add`` per leaf.
* ``fused``   — :func:`segment_reduce_fused`, dtype-grouped single scatter
  (the CPU winner: XLA CPU pays per scatter op, not per column).
* ``sorted``  — :func:`segment_reduce_sorted`, argsort + cumsum + diff
  (integer leaves only; exact by wraparound cancellation).
* ``tiled[b,kb]`` — the Pallas kernel of ``kernel.py`` over the
  ``TILINGS`` ladder.  Offered on TPU only (in interpret mode each grid
  step is Python, so it can never win), and only while its dense one-hot
  work ``n * num_keys`` stays within :data:`MAX_DENSE_CELLS`: beyond
  that one candidate would run for minutes at trace time (a k=12 k-mer
  table is ~1.5e8 records x 1.7e7 keys).  Every tiling fits the
  compiler's scoped VMEM limit (``tests/test_chip_compile.py`` compiles
  each one for a v5e).

A candidate that raises fails the tune: a strategy that cannot run is a
fault to fix, not a timing to skip.

``REPRO_SEGMENT_AUTOTUNE=0`` skips measurement and returns the static
heuristic (:func:`_default_strategy`, under the same dense-work bound).
At the smoke's k=12 size on a v5e, tuning takes ~5 minutes of trace time
(three candidates at 1.6e8 records, compiled and run four times each).

:func:`tune_report` exposes everything tried this process (chosen
strategy, per-candidate timings) — ``benchmarks/kmer.py`` embeds it in
``BENCH_kmer.json`` and ``benchmarks/summary.py`` renders the tiling
table from it.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import VMEM_LIMIT_BYTES, use_interpret
from repro.kernels.segment_reduce.kernel import (DEFAULT_BLOCK,
                                                DEFAULT_KEY_BLOCK, tiling,
                                                vmem_bytes)

#: strategies a Strategy.name may take (``tiled`` carries block params too)
STRATEGIES = ("scatter", "fused", "sorted", "tiled")

#: (block, key_block) tilings the tuner tries for the Pallas kernel
TILINGS = ((1024, 1024), (1024, 4096), (2048, 4096), (2048, 8192))

#: Most one-hot cells (records x keys) a tiled candidate may cost.  At the
#: MXU rate of a v5e (~1e12 cells/s for this kernel's shape) 2**40 cells
#: is about a second; the kernel does this work for any key order.
MAX_DENSE_CELLS = 1 << 40

_WARMUP = 1
_REPS = 3

# cache + report, process-wide.  Keyed by _cache_key(); values are
# (strategy_name, block, key_block).
_CACHE: Dict[Tuple, Tuple[str, int, int]] = {}
_REPORT: List[Dict[str, Any]] = []


def _leaf_signature(values: Any) -> Tuple:
    return tuple((tuple(leaf.shape[1:]), jnp.dtype(leaf.dtype).name)
                 for leaf in jax.tree.leaves(values))


def _cache_key(backend: str, op: str, n: int, num_keys: int,
               leaf_sig: Tuple) -> Tuple:
    return (backend, op, n, num_keys, leaf_sig)


def _all_int_leaves(leaf_sig: Tuple) -> bool:
    return all(np.issubdtype(np.dtype(name), np.integer)
               for _, name in leaf_sig)


def _columns(leaf_sig: Tuple) -> int:
    return sum(int(np.prod(shape)) for shape, _ in leaf_sig)


def tiled_feasible(n: int, num_keys: int) -> bool:
    """Whether the tiled kernel's dense one-hot work is within bounds."""
    return n * num_keys <= MAX_DENSE_CELLS


def _vmem_fits(block: int, key_block: int, ncols: int) -> bool:
    return vmem_bytes(block, key_block, ncols) <= VMEM_LIMIT_BYTES


@jax.jit
def _stride_keys(idx: jax.Array, num_keys: jax.Array) -> jax.Array:
    return ((idx * jnp.uint32(2654435761)) % num_keys).astype(jnp.int32)


def _synthetic(n: int, num_keys: int, leaf_sig: Tuple):
    """Concrete sample problem matching the traced shapes, made on the
    device.

    Keys are a fixed permutation-ish pattern (golden-ratio stride) so every
    strategy sees realistic scatter conflicts; no RNG, so tuning is
    deterministic per shape.
    """
    keys = _stride_keys(jnp.arange(n, dtype=jnp.uint32),
                        jnp.uint32(max(num_keys, 1)))
    leaves = [jnp.ones((n,) + shape, np.dtype(name))
              for shape, name in leaf_sig]
    valid = jnp.ones((n,), bool)
    return keys, leaves, valid


def _time_callable(fn, *args) -> float:
    for _ in range(_WARMUP):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(_REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / _REPS


def _candidates(backend: str, op: str, n: int, num_keys: int,
                leaf_sig: Tuple) -> List[Tuple[str, int, int]]:
    cands: List[Tuple[str, int, int]] = [("fused", 0, 0), ("scatter", 0, 0)]
    if _all_int_leaves(leaf_sig):
        cands.append(("sorted", 0, 0))
    if backend == "tpu" and tiled_feasible(n, num_keys):
        for block, key_block in TILINGS:
            if _vmem_fits(block, key_block, _columns(leaf_sig)):
                cands.append(("tiled",) + tiling(n, num_keys, block,
                                                 key_block))
    return list(dict.fromkeys(cands))       # tilings may clamp alike


def _default_strategy(backend: str, n: int,
                      num_keys: int) -> Tuple[str, int, int]:
    """The untimed pick.  On TPU: the tiled kernel where its dense work is
    bounded, else the plain scatter (the tuner's pick for a k=12 k-mer
    table on a v5e, 3.3x faster than fused and 3.8x than sorted at 1.6e8
    records).  Elsewhere the fused scatter."""
    if backend != "tpu":
        return ("fused", 0, 0)
    if tiled_feasible(n, num_keys):
        return ("tiled",) + tiling(n, num_keys, DEFAULT_BLOCK,
                                   DEFAULT_KEY_BLOCK)
    return ("scatter", 0, 0)


def pick_strategy(op: str, n: int, num_keys: int, values: Any,
                  backend: Optional[str] = None) -> Tuple[str, int, int]:
    """Return ``(strategy, block, key_block)`` for this problem shape.

    Measured once per (backend, op, shape signature) and cached for the
    process; safe to call from inside a trace (tuning runs its own jits on
    concrete synthetic arrays).  Non-sum monoids always resolve to
    ``scatter`` — the fused/sorted/tiled paths are sum-only.
    """
    if op != "sum":
        return ("scatter", 0, 0)
    backend = backend or jax.default_backend()
    leaf_sig = _leaf_signature(values)
    key = _cache_key(backend, op, n, num_keys, leaf_sig)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    if os.environ.get("REPRO_SEGMENT_AUTOTUNE") == "0" or n == 0:
        choice = _default_strategy(backend, n, num_keys)
        _CACHE[key] = choice
        return choice
    choice = _measure(key, op, n, num_keys, leaf_sig, backend)
    _CACHE[key] = choice
    return choice


def _measure(key: Tuple, op: str, n: int, num_keys: int, leaf_sig: Tuple,
             backend: str) -> Tuple[str, int, int]:
    from repro.kernels.segment_reduce import ops as _ops
    from repro.obs import TRACER

    rows: List[Dict[str, Any]] = []
    best: Optional[Tuple[float, Tuple[str, int, int]]] = None
    # the tuner runs while the caller's program is being traced; without
    # eval context the candidates would be staged into that trace and
    # the clock would time tracing, not execution
    with TRACER.span("segment_reduce.autotune", n=n, num_keys=num_keys,
                     backend=backend), jax.ensure_compile_time_eval():
        keys, leaves, valid = _synthetic(n, num_keys, leaf_sig)
        values = tuple(leaves)
        for strat, block, key_block in _candidates(backend, op, n, num_keys,
                                                   leaf_sig):
            def run(k, v, m, _s=strat, _b=block, _kb=key_block):
                return _ops.segment_reduce_impl(
                    k, v, num_keys, op=op, valid=m, strategy=_s,
                    block=_b, key_block=_kb,
                    interpret=use_interpret())
            dt = _time_callable(run, keys, values, valid)
            label = (f"tiled[{block},{key_block}]" if strat == "tiled"
                     else strat)
            rows.append({"candidate": label, "ms": dt * 1e3})
            if best is None or dt < best[0]:
                best = (dt, (strat, block, key_block))
    choice = best[1]
    _REPORT.append({
        "backend": backend, "op": op, "n": n, "num_keys": num_keys,
        "leaves": [list(map(str, sig)) for sig in leaf_sig],
        "chosen": (f"tiled[{choice[1]},{choice[2]}]"
                   if choice[0] == "tiled" else choice[0]),
        "block": choice[1], "key_block": choice[2],
        "candidates": rows,
    })
    return choice


def tune_report() -> List[Dict[str, Any]]:
    """Everything tuned this process: one entry per distinct shape with the
    chosen strategy and all candidate timings (JSON-serializable)."""
    return list(_REPORT)


def clear_cache() -> None:
    """Drop tuning decisions + report (tests use this for isolation)."""
    _CACHE.clear()
    _REPORT.clear()
