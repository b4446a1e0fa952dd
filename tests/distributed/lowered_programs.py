"""Lowered programs of the one-word k-mer jobs, for
``tests/test_lowered_programs.py``.

    python tests/distributed/lowered_programs.py [<out_dir>]

Builds ``map(kmer-stats k) -> reduce_by_key(field0, value_by=field1,
op="sum")`` (the table inferred from the key space, as the k-mer cells
of ``BENCHMARK.json`` build it) over the cells' record shapes, 1,048,576
reads of width 160 a device, for k = 6 and 12 on 1, 2 and 4 CPU
devices, and lowers each program without running it. Prints one JSON
object ``{"k<k>.d<devices>": sha256 of the lowered StableHLO text}``
and writes each text to ``<out_dir>/k<k>.d<devices>.mlir`` when an
output directory is given. The segment reduce takes its static default
(``REPRO_SEGMENT_AUTOTUNE=0``), as the benchmark's runs do.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_SEGMENT_AUTOTUNE"] = "0"
import hashlib
import json
import sys

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import MaRe, PlanCache
from repro.core.dataset import ShardedDataset
from repro.core.planner import compile_plan

READS = 1 << 20
WIDTH = 160


def field0(recs):
    return recs[0]


def field1(recs):
    return (recs[1],)


def lowered_text(k: int, devices: int) -> str:
    mesh = compat.make_mesh((devices,), ("data",),
                            devices=jax.devices()[:devices])
    records = {"data": jax.ShapeDtypeStruct((devices * READS, WIDTH),
                                            jnp.uint8),
               "len": jax.ShapeDtypeStruct((devices * READS,), jnp.int32)}
    counts = jax.ShapeDtypeStruct((devices,), jnp.int32)
    ds = ShardedDataset(records=records, counts=counts, mesh=mesh)
    m = MaRe(ds).map(image="kmer-stats", k=k).reduce_by_key(
        field0, value_by=field1, op="sum")
    prog = compile_plan(m.plan, ds, PlanCache())
    return prog.fn.lower(records, counts).as_text()


if __name__ == "__main__":
    assert jax.device_count() == 4
    out_dir = sys.argv[1] if len(sys.argv) > 1 else None
    digests = {}
    for k in (6, 12):
        for devices in (1, 2, 4):
            name = f"k{k}.d{devices}"
            text = lowered_text(k, devices)
            digests[name] = hashlib.sha256(text.encode()).hexdigest()
            if out_dir is not None:
                with open(os.path.join(out_dir, f"{name}.mlir"), "w") as f:
                    f.write(text)
    print(json.dumps(digests, sort_keys=True))
