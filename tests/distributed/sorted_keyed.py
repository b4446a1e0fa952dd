"""Sorted keyed stages (two-word keys) on a 4-device mesh, for
``tests/test_sorted_keyed.py``.

    python tests/distributed/sorted_keyed.py <out_dir>

Writes, for each case of ``CASES``, the inputs and the 4-device result
to ``<out_dir>/<case>.npz``, and ``<out_dir>/facts.json``: the op scopes,
``stage0.sorted_keyed``, ``stage0.local_keyed`` and
``stage0.distinct_keys`` of the combiner-on program.  The test runs the
same inputs on its own one-device mesh and against a host group-by.
"""
import os
if __name__ == "__main__":
    # a 4-device CPU mesh for the child; tests/test_sorted_keyed.py
    # imports CASES and MODES from here and keeps its own devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys

import jax
import numpy as np

from repro import compat
from repro.core import MaRe, PlanCache, from_host

MODES = {"combiner": {}, "nocombiner": {"combiner": False}}
MIXED = [f"mixed-sum-{dtype}-{mode}" for dtype in ("int32", "uint8")
         for mode in MODES]
#: empty: no record on any shard; sparse: 3 records, the fourth shard
#: empty; onekey: every record one key; distinct: no key twice; highword:
#: keys equal in the low word; top: the largest key, (2**32-1, 2**32-1),
#: among others
EDGES = ["empty", "sparse", "onekey", "distinct", "highword", "top"]
CASES = MIXED + [f"{edge}-sum-int32-{mode}" for edge in EDGES
                 for mode in MODES]


def case_data(case: str):
    """``(keys uint32 [n, 2], values [n])``; uint8 sums of a hot key
    wrap, as integer sums do."""
    kind, _, dtype, _ = case.split("-")
    rng = np.random.default_rng(23)
    n = 600
    pool = rng.integers(0, 2 ** 32, (40, 2), dtype=np.uint64)
    pool[1] = [pool[0, 0] + 1, pool[0, 1]]          # only the high word
    pool[2] = [pool[0, 0], pool[0, 1] + 1]          # only the low word
    pool = pool.astype(np.uint32)
    if kind == "mixed":
        pick = np.where(rng.random(n) < 0.5, 7, rng.integers(0, 40, n))
        keys = pool[pick]
    elif kind == "empty":
        keys = np.zeros((0, 2), np.uint32)
    elif kind == "sparse":
        keys = pool[[3, 5, 3]]
    elif kind == "onekey":
        keys = np.repeat(pool[:1], n, axis=0)
    elif kind == "distinct":
        keys = np.stack([np.arange(n, dtype=np.uint32),
                         rng.integers(0, 2 ** 32, n, dtype=np.uint64)
                         .astype(np.uint32)], axis=1)
    elif kind == "highword":
        keys = np.stack([rng.integers(0, 9, n).astype(np.uint32),
                         np.full(n, 12345, np.uint32)], axis=1)
    elif kind == "top":
        keys = pool[rng.integers(0, 4, n)]
        keys[::3] = 2 ** 32 - 1
    else:
        raise ValueError(case)
    if dtype == "uint8":
        return keys, rng.integers(0, 256, keys.shape[0]).astype(np.uint8)
    return keys, rng.integers(-50, 50, keys.shape[0]).astype(np.int32)


def key_first(recs):
    return recs[0]


def value_second(recs):
    return (recs[1],)


def run(mesh, case: str):
    _, op, _, mode = case.split("-")
    keys, vals = case_data(case)
    capacity = 2 if keys.shape[0] == 0 else None
    m = MaRe(from_host((keys, vals), mesh, capacity=capacity),
             plan_cache=PlanCache())
    q = m.reduce_by_key(key_first, value_by=value_second, op=op,
                        **MODES[mode])
    out_keys, (out_vals,), out_counts = q.collect()
    return (keys, vals), (out_keys, out_vals, out_counts), q


if __name__ == "__main__":
    assert jax.device_count() == 4
    out_dir = sys.argv[1]
    mesh = compat.make_mesh((4,), ("data",))
    for case in CASES:
        (keys, vals), (ok, ov, oc), q = run(mesh, case)
        np.savez(os.path.join(out_dir, f"{case}.npz"), keys=keys,
                 vals=vals, out_keys=ok, out_vals=ov, out_counts=oc)
        if case == "mixed-sum-int32-combiner":
            (prog,) = q.plan_cache.programs()
            d = q.report().diagnostics
            facts = {"scopes": sorted(set(prog.op_scopes().values())),
                     **{k: d[f"stage0.{k}"] for k in (
                         "sorted_keyed", "local_keyed", "distinct_keys",
                         "shuffle_dropped")}}
    with open(os.path.join(out_dir, "facts.json"), "w") as f:
        json.dump(facts, f)
    print("OK")
