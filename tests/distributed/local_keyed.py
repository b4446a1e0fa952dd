"""Keyed stages on a 4-device mesh, for ``tests/test_local_keyed.py``.

    python tests/distributed/local_keyed.py <out_dir>

Writes, for each case of ``CASES``, the inputs and the 4-device result
to ``<out_dir>/<case>.npz``, and ``<out_dir>/facts.json``: the op scopes
and ``stage0.local_keyed`` of the combiner-on program.  The test runs
the same inputs on its own one-device mesh and compares.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys

import jax
import numpy as np

from repro import compat
from repro.core import MaRe, PlanCache, from_host

NUM_KEYS = 48
MODES = {"combiner": {}, "nocombiner": {"combiner": False},
         "salt8": {"combiner": False, "salt": 8}}
CASES = [f"{mode}-{op}-{dtype}" for mode in MODES
         for op in ("sum", "max", "min") for dtype in ("int32", "float32")]


def case_data(dtype: str):
    """600 records over 40 of 48 keys, half of them on one hot key;
    float values are quarters, so every sum is exact in any order."""
    rng = np.random.default_rng(17)
    keys = np.where(rng.random(600) < 0.5, 7,
                    rng.integers(0, 40, 600)).astype(np.int32)
    vals = rng.integers(-50, 50, 600)
    vals = (vals.astype(np.int32) if dtype == "int32"
            else (vals / 4).astype(np.float32))
    return keys, vals


def key_first(recs):
    return recs[0]


def value_second(recs):
    return (recs[1],)


def run(mesh, case: str):
    mode, op, dtype = case.split("-")
    keys, vals = case_data(dtype)
    m = MaRe(from_host((keys, vals), mesh), plan_cache=PlanCache())
    q = m.reduce_by_key(key_first, value_by=value_second, op=op,
                        num_keys=NUM_KEYS, **MODES[mode])
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
        if case == "combiner-sum-int32":
            (prog,) = q.plan_cache.programs()
            facts = {"scopes": sorted(set(prog.op_scopes().values())),
                     "local_keyed":
                         q.report().diagnostics["stage0.local_keyed"]}
    with open(os.path.join(out_dir, "facts.json"), "w") as f:
        json.dump(facts, f)
    print("OK")
