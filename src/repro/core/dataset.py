"""ShardedDataset: the RDD analogue — a partitioned dataset on a mesh axis.

A dataset is a pytree of *global* arrays whose leading dimension is the
total record capacity, sharded over one mesh axis (`NamedSharding`), plus a
per-shard valid-record count.  Shards play the role of RDD partitions;
`from_host` plays the role of `sc.parallelize`, `collect` of `RDD.collect`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import span


@dataclasses.dataclass
class ShardedDataset:
    records: Any          # pytree of global arrays; leading dim = n * cap
    counts: jax.Array     # [n_shards] int32, valid records per shard
    mesh: Mesh
    axis: str = "data"
    #: Lineage fingerprint (repro.runtime.lineage.Lineage) identifying how
    #: this dataset was produced — root source id + canonical stage
    #: signatures.  None = unknown provenance; the runtime executor
    #: assigns a fresh host root on first action, so forked handles over
    #: the same base dataset share a lineage prefix.
    lineage: Any = None

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def capacity(self) -> int:
        """Per-shard record capacity."""
        lead = jax.tree.leaves(self.records)[0].shape[0]
        return lead // self.num_shards

    def record_spec(self) -> Any:
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), self.records)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis))

    def with_records(self, records: Any, counts: Optional[jax.Array] = None
                     ) -> "ShardedDataset":
        # records changed by an unknown transformation -> provenance lost
        return dataclasses.replace(
            self, records=records, lineage=None,
            counts=self.counts if counts is None else counts)


def from_host(records: Any, mesh: Mesh, axis: str = "data",
              capacity: Optional[int] = None) -> ShardedDataset:
    """Distribute host records round-robin-block over the ``axis`` shards,
    padding each shard to a common capacity (static SPMD shapes)."""
    n = int(mesh.shape[axis])
    leaves = jax.tree.leaves(records)
    total = leaves[0].shape[0]
    cap = capacity or math.ceil(total / n)
    counts = np.full((n,), cap, np.int32)
    rem = n * cap - total
    for i in range(rem):
        counts[n - 1 - (i % n)] -= 1
    # Block layout: shard s holds records [sum(counts[:s]), +counts[s]) of
    # the input, padded to cap.
    offsets = np.concatenate([[0], np.cumsum(counts)])

    def place(leaf):
        leaf = np.asarray(leaf)
        out = np.zeros((n * cap,) + leaf.shape[1:], leaf.dtype)
        for s in range(n):
            c = counts[s]
            out[s * cap:s * cap + c] = leaf[offsets[s]:offsets[s] + c]
        return jax.device_put(out, NamedSharding(mesh, P(axis)))

    placed = jax.tree.map(place, records)
    counts_dev = jax.device_put(
        jnp.asarray(counts), NamedSharding(mesh, P(axis)))
    return ShardedDataset(records=placed, counts=counts_dev, mesh=mesh,
                          axis=axis)


def from_shard_arrays(shard_records: Any, shard_counts: Sequence[int],
                      mesh: Mesh, axis: str = "data") -> ShardedDataset:
    """Assemble a ShardedDataset from per-shard host pytrees.

    ``shard_records`` is an iterable of ``num_shards`` pytrees whose leaves
    are ``[cap, ...]`` arrays (identical cap/dtype/trailing shape across
    shards).  Each shard's leaves are ``jax.device_put`` to that shard's
    device(s) as they arrive — transfers are dispatched asynchronously, so
    when the iterable packs lazily (repro.io.ingest), the device transfer
    of shard *s* overlaps host packing of shard *s+1* (double buffering) —
    then stitched into global arrays without a host-side copy of the full
    dataset.
    """
    n = int(mesh.shape[axis])
    sharding = NamedSharding(mesh, P(axis))
    axis_idx = list(mesh.axis_names).index(axis)
    dev_grid = np.moveaxis(np.asarray(mesh.devices), axis_idx, 0
                           ).reshape(n, -1)

    treedef = None
    leaf_shards: List[List[Any]] = []
    count_shards: List[Any] = []
    num_seen = 0
    for s, rec in enumerate(shard_records):
        leaves, td = jax.tree.flatten(rec)
        if treedef is None:
            treedef = td
            leaf_shards = [[] for _ in leaves]
        for li, leaf in enumerate(leaves):
            leaf = np.asarray(leaf)
            for d in dev_grid[s]:
                leaf_shards[li].append(jax.device_put(leaf, d))
        cnt = np.asarray([shard_counts[s]], np.int32)
        for d in dev_grid[s]:
            count_shards.append(jax.device_put(cnt, d))
        num_seen += 1
    if num_seen != n:
        raise ValueError(f"got {num_seen} shard pytrees for {n} shards")

    def assemble(arrays, lead, tail):
        return jax.make_array_from_single_device_arrays(
            (lead,) + tuple(tail), sharding, arrays)

    out_leaves = []
    for li, arrays in enumerate(leaf_shards):
        cap_shape = arrays[0].shape
        out_leaves.append(assemble(arrays, n * cap_shape[0], cap_shape[1:]))
    records = jax.tree.unflatten(treedef, out_leaves)
    counts = assemble(count_shards, n, ())
    return ShardedDataset(records=records, counts=counts, mesh=mesh,
                          axis=axis)


def collect_shard(ds: ShardedDataset, shard: int = 0) -> Any:
    """One shard's valid records (``MaRe.collect(shard=...)``'s engine).

    Slices the shard's block on device and transfers only its valid rows
    to host — a replicated reduce result would otherwise ship every
    shard's full copy across just to keep one.  Runs under the
    ``collect.to_host`` span (args: shard, records, bytes).
    """
    n = ds.num_shards
    if not 0 <= shard < n:
        raise ValueError(f"shard index {shard} out of range for "
                         f"{n}-shard dataset")
    with span("collect.to_host", shard=shard) as sp:
        rows = int(jax.device_get(ds.counts)[shard])

        def one(leaf):
            cap = leaf.shape[0] // n  # per-leaf shard block
            lo = shard * cap
            return jax.device_get(leaf[lo:lo + min(cap, rows)])

        out = jax.tree.map(one, ds.records)
        sp.set(records=rows, bytes=_nbytes(out))
    return out


def collect_first_shard(ds: ShardedDataset) -> Any:
    """Shard 0's valid records (for reduced/replicated results)."""
    return collect_shard(ds, 0)


def collect(ds: ShardedDataset) -> Any:
    """Gather valid records to host (RDD.collect), under the
    ``collect.to_host`` span (args: records, bytes)."""
    with span("collect.to_host") as sp:
        counts = np.asarray(jax.device_get(ds.counts))
        cap = ds.capacity

        def gather(leaf):
            host = np.asarray(jax.device_get(leaf))
            segs: List[np.ndarray] = []
            for s in range(ds.num_shards):
                segs.append(host[s * cap:s * cap + counts[s]])
            return np.concatenate(segs, axis=0) if segs else host[:0]

        out = jax.tree.map(gather, ds.records)
        sp.set(records=int(counts.sum()), bytes=_nbytes(out))
    return out


def _nbytes(tree: Any) -> int:
    return sum(int(np.asarray(leaf).nbytes) for leaf in jax.tree.leaves(tree))
