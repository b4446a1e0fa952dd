"""Run one cell at its rehearsal size with a fault planted under the
timed path, and print the harness's result line.

    python bench/tests/fault_run.py <fault> --workload <cell> --seed 5 \
        --seconds 1 --trace 0 --rehearse

Faults (each must turn ``correct`` false):

* ``half``: ingest keeps half of each shard's records;
* ``exchange``: the keyed hash exchange leaves every record on the chip
  that made it;
* ``alter``: ``collect`` hands back one count changed by one.
"""
from __future__ import annotations

import importlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def plant(fault: str) -> None:
    if fault == "half":
        ingest = importlib.import_module("repro.io.ingest")
        real = ingest.from_shard_arrays

        def halved(records, counts, mesh, axis="data"):
            return real(records, [c // 2 for c in counts], mesh, axis)

        ingest.from_shard_arrays = halved
    elif fault == "exchange":
        import jax.numpy as jnp

        import repro.core.planner as planner
        from repro.core.shuffle import ShuffleResult

        def local(part, keys, axis_name, axis_size, capacity=None,
                  partitioner=None, dest=None):
            return ShuffleResult(part, jnp.int32(0),
                                 jnp.zeros((axis_size,), jnp.int32))

        planner.shuffle_partition = local
    elif fault == "alter":
        import numpy as np

        import repro.core.mare as mare
        real = mare._finalizer

        def altered(shard):
            fin = real(shard)

            def out(ds):
                got = fin(ds)
                if len(got) == 3:                     # keys, values, counts
                    keys, values, counts = got
                    counts = np.array(counts)
                    counts[0] += 1
                    return keys, values, counts
                (total,) = got
                return (np.asarray(total) + 1,)
            return out

        mare._finalizer = altered
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    from bench.run import main
    sys.exit(main(sys.argv[2:]))
