"""Compile the main path for a described TPU v5e, with no chip attached.

Interpret mode (the rest of the suite) says nothing about what Mosaic
and the TPU compiler accept; these tests ask the real compiler.  They
compile the tiled segment-sum kernel at ``chip_smoke.py``'s k=6 shape for
every autotuner tiling, and the planner's whole fused k-mer programs at
the smoke's size.  Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a module fixture: only one process may
load the TPU library, so describing it while a module is imported would
make test collection differ between pytest-xdist workers.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import MaRe, PlanCache, ShardedDataset, compile_plan
from repro.kernels.segment_reduce import ops as seg_ops
from repro.kernels.segment_reduce.kernel import segment_sum_tiled
from repro.kernels.segment_reduce.tune import TILINGS

#: chip_smoke.py's input: 2**20 reads, packed to width 160 by ingest
READS, WIDTH = 1 << 20, 160
#: HBM of one v5e chip
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _key_of(recs):
    return recs[0]


def _ones_of(recs):
    return (recs[1],)


@pytest.mark.parametrize("block,key_block", TILINGS)
def test_tiled_kernel_compiles_for_v5e(one_chip, block, key_block):
    n = READS * (WIDTH - 6 + 1)                  # k=6 windows per read
    keys = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    ones = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    fn = jax.jit(lambda k, v, m: segment_sum_tiled(
        k, (v,), 4 ** 6, m, block=block, key_block=key_block,
        interpret=False))
    compiled = fn.lower(keys, ones, valid).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kmer_program(topo, k: int, use_kernel):
    """The planner's fused ``kmer-stats -> reduce_by_key`` program for
    the smoke's dataset on one described chip."""
    tiny = {"data": np.zeros((8, WIDTH), np.uint8),
            "len": np.zeros(8, np.int32)}
    plan = (MaRe(tiny).map(image="kmer-stats", k=k)
            .reduce_by_key(_key_of, value_by=_ones_of,
                           use_kernel=use_kernel)).plan
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    records = {
        "data": jax.ShapeDtypeStruct((READS, WIDTH), jnp.uint8,
                                     sharding=sharded),
        "len": jax.ShapeDtypeStruct((READS,), jnp.int32, sharding=sharded)}
    counts = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sharded)
    ds = ShardedDataset(records=records, counts=counts, mesh=mesh)
    prog = compile_plan(plan, ds, cache=PlanCache())
    prog.ensure_compiled(records, counts)
    return prog


def _device_bytes(prog) -> int:
    mem = prog._aot.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


@pytest.mark.parametrize("strategy", ["fused", "sorted", "scatter"])
def test_kmer12_program_fits_v5e(topo, monkeypatch, strategy):
    # the autotuner would time candidates on this host's CPU backend;
    # pin each strategy it may pick on the chip instead
    monkeypatch.setattr(seg_ops, "pick_strategy",
                        lambda *a, **kw: (strategy, 0, 0))
    prog = _kmer_program(topo, 12, None)
    assert _device_bytes(prog) < HBM_BYTES


def test_kmer6_forced_tiled_program_holds_kernel(topo, monkeypatch):
    # on this CPU backend the kernel would otherwise lower in interpret
    # mode; the chip compiles it
    monkeypatch.setattr(seg_ops, "use_interpret", lambda: False)
    prog = _kmer_program(topo, 6, True)
    assert "tpu_custom_call" in prog.as_text()
    assert _device_bytes(prog) < HBM_BYTES
