"""Key-based repartitioning — MaRe's ``repartitionBy`` primitive.

Paper semantics (§1.2.1/§1.2.2): a user ``keyBy`` function computes a key
per record; ``repartition`` + ``HashPartitioner`` then guarantees records
with equal keys land in the same partition.

TPU mapping: partitions are shards on a mesh axis of size ``n``.  Each shard
hashes its record keys, packs records into a ``[n, capacity, ...]`` send
buffer grouped by destination, and a single ``lax.all_to_all`` performs the
shuffle.  Fixed capacity is the SPMD price for static shapes — the same
capacity-factor discipline used by MoE dispatch (which *is* this primitive
with ``keyBy = router``; see models/moe.py).  Overflow is counted and
surfaced, never silently ignored.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.container import Partition, make_partition


def hash_keys(keys: jax.Array) -> jax.Array:
    """Deterministic 32-bit integer mix (splitmix32-style) — the
    HashPartitioner.  Accepts any integer dtype, returns uint32."""
    x = keys.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def hash_key_words(keys: jax.Array) -> jax.Array:
    """The HashPartitioner of a two-word key (``[n, 2]``, high then low):
    :func:`hash_keys` of the low word mixed with the hash of the high
    one, so keys that differ in either word spread.  Returns uint32."""
    return hash_keys(keys[:, 1].astype(jnp.uint32) ^ hash_keys(keys[:, 0]))


def keyed_bucket_capacities(num_keys: int, axis_size: int) -> np.ndarray:
    """Exact per-destination bucket sizes of the keyed hash exchange.

    The hash partitioner is deterministic and the key space is bounded, so
    how many of the ``num_keys`` possible keys each destination shard owns
    is computable statically on the host: entry ``d`` is
    ``|{k in [0, num_keys) : hash(k) % axis_size == d}|``.  A combiner-side
    shard sends at most one record per distinct key, so entry ``d`` bounds
    what *any* shard can send to ``d`` — the skew-aware capacity vector.
    Runs chunked so a 4**15-sized key space costs MiBs of host scratch,
    not GiBs.  (Host-side mirror of :func:`hash_keys`; keep in lockstep.)
    """
    if axis_size == 1:                # every key's owner is shard 0
        return np.array([num_keys], np.int64)
    mask = np.uint64(0xFFFFFFFF)
    buckets = np.zeros((axis_size,), np.int64)
    chunk = 1 << 22
    for start in range(0, num_keys, chunk):
        x = np.arange(start, min(start + chunk, num_keys), dtype=np.uint64)
        x = ((x ^ (x >> np.uint64(16))) * np.uint64(0x7FEB352D)) & mask
        x = ((x ^ (x >> np.uint64(15))) * np.uint64(0x846CA68B)) & mask
        x = x ^ (x >> np.uint64(16))
        dest = (x % np.uint64(axis_size)).astype(np.int64)
        buckets += np.bincount(dest, minlength=axis_size)
    return buckets


def keyed_bucket_capacity(num_keys: int, axis_size: int) -> int:
    """Exact-lossless *uniform* per-destination send capacity for a
    combined keyed exchange: ``max(keyed_bucket_capacities(...))``.

    Contract: a single ``lax.all_to_all`` under static SPMD must use ONE
    capacity for every (source, destination) pair — shapes are uniform
    across shards — so the exchange buffer is sized to the *largest* hash
    bucket even though most destinations own fewer keys.  Typically
    ~``num_keys / axis_size`` instead of the worst-case ``num_keys`` a
    dynamic bound would have to assume; the gap between this max and the
    mean of :func:`keyed_bucket_capacities` is the (mild) hash-imbalance
    cost, and is unrelated to *data* skew — a hot key inflates record
    counts, not distinct-key counts, which is why the combiner (or the
    salted two-hop path for ``combiner=False``; see
    ``planner._apply_keyed``) is the skew defense, not this bound.
    Overflow semantics: sends beyond capacity are counted into
    ``ShuffleResult.dropped`` and raise at action time; with this bound
    on a combined exchange the counter is provably always zero.
    """
    return max(1, int(keyed_bucket_capacities(num_keys, axis_size).max()))


def salted_dest(keys: jax.Array, axis_size: int, salt: int) -> jax.Array:
    """Hot-key-splitting destination map: spread each key's records over
    ``salt`` consecutive shards round-robin by record slot.

    ``dest = (hash(key) + (slot % salt)) % axis_size`` — a key's records
    land on a deterministic window of ``salt`` shards instead of one, so
    a 90%-hot key costs any single destination ~``n*0.9/salt`` slots
    rather than ``n*0.9``.  Equal keys no longer co-locate after ONE
    exchange; callers must follow with a per-key merge and a second,
    combiner-style exchange (the two-hop path in ``planner._apply_keyed``).
    """
    base = hash_keys(keys)
    slot = jnp.arange(keys.shape[0], dtype=jnp.uint32)
    return ((base + slot % jnp.uint32(salt))
            % jnp.uint32(axis_size)).astype(jnp.int32)


class ShuffleResult(NamedTuple):
    part: Partition         # received records, compacted to the front
    dropped: jax.Array      # int32 scalar: records lost to capacity overflow
    send_counts: jax.Array  # [n] records sent to each destination shard


class PackResult(NamedTuple):
    buffer: Any             # [num_dest, capacity, ...] pytree
    counts: jax.Array       # [num_dest] records packed per destination
    dropped: jax.Array      # overflow count
    dest: jax.Array         # [n] destination of each input record
    pos: jax.Array          # [n] slot of each input record at its dest
    in_cap: jax.Array       # [n] whether the record made it into the buffer


def _pack_by_dest(records: Any, dest: jax.Array, valid: jax.Array,
                  num_dest: int, capacity: int) -> PackResult:
    """Group records into a [num_dest, capacity, ...] send buffer.

    GATHER-ONLY construction: sort by destination, then each output slot
    (d, p) *gathers* sorted row ``start[d] + p``.  No scatter ops — XLA's
    scatter expander materializes full-buffer u32/f32 temporaries (a
    measured dominant memory cost; EXPERIMENTS.md §Perf kimi-2).  Stable
    order within a destination mirrors Spark's deterministic partitioning.
    The returned (dest, pos, in_cap) triple lets callers invert the pack
    with another pure gather.
    """
    cap_in = dest.shape[0]
    dest_m = jnp.where(valid, dest, num_dest)  # invalid -> sentinel bucket
    order = jnp.argsort(dest_m, stable=True)
    sorted_dest = dest_m[order]
    # start offset of each destination bucket in the sorted stream
    start = jnp.searchsorted(sorted_dest, jnp.arange(num_dest + 1))
    counts = start[1:] - start[:-1]           # true per-dest counts
    dropped = jnp.sum(jnp.maximum(counts - capacity, 0))
    counts_c = jnp.minimum(counts, capacity)
    # output slot (d, p) <- sorted row start[d] + p   (gather indices)
    src_pos = start[:num_dest, None] + jnp.arange(capacity)[None, :]
    slot_ok = jnp.arange(capacity)[None, :] < counts_c[:, None]
    src_pos = jnp.where(slot_ok, src_pos, cap_in)       # sentinel row

    def build(leaf):
        sorted_leaf = jnp.take(leaf, order, axis=0, mode="clip")
        ext = jnp.concatenate(
            [sorted_leaf,
             jnp.zeros((1,) + leaf.shape[1:], leaf.dtype)], axis=0)
        return jnp.take(ext, src_pos.reshape(-1), axis=0, mode="clip").reshape(
            (num_dest, capacity) + leaf.shape[1:])

    buffer = jax.tree.map(build, records)
    # per-record placement in original order (inverse permutation)
    pos_sorted = jnp.arange(cap_in) - start[
        jnp.clip(sorted_dest, 0, num_dest)]
    in_cap_sorted = (pos_sorted < capacity) & (sorted_dest < num_dest)
    inv = jnp.argsort(order)                  # order is a permutation
    pos = jnp.take(pos_sorted, inv, mode="clip")
    in_cap = jnp.take(in_cap_sorted, inv, mode="clip")
    return PackResult(buffer=buffer, counts=counts_c, dropped=dropped,
                      dest=jnp.where(valid, dest, num_dest), pos=pos,
                      in_cap=in_cap)


def unpack_gather(packed_flat: jax.Array, pack: PackResult,
                  capacity: int) -> jax.Array:
    """Inverse of _pack_by_dest for one leaf: returns, per input record,
    the row of ``packed_flat`` ([num_dest * capacity, ...], sentinel-safe)
    it was packed into (zeros for dropped records).  Pure gather."""
    n_slots = packed_flat.shape[0]
    ext = jnp.concatenate(
        [packed_flat,
         jnp.zeros((1,) + packed_flat.shape[1:], packed_flat.dtype)],
        axis=0)
    idx = jnp.where(pack.in_cap, pack.dest * capacity + pack.pos, n_slots)
    return jnp.take(ext, idx, axis=0, mode="clip")


def shuffle_partition(
    part: Partition,
    keys: jax.Array,
    axis_name: str,
    axis_size: int,
    capacity: Optional[int] = None,
    partitioner: Callable[[jax.Array], jax.Array] = hash_keys,
    dest: Optional[jax.Array] = None,
) -> ShuffleResult:
    """shard_map-interior repartitionBy over ``axis_name``.

    ``keys``: int array [capacity_in] (entries beyond ``part.count`` are
    ignored).  Output partition capacity is ``axis_size * capacity`` (every
    source may contribute up to ``capacity`` records).  With ``capacity ==
    part.capacity`` the shuffle is lossless (a single source can never
    overflow a destination).  ``dest`` (int32 [capacity_in], values in
    ``[0, axis_size)``) overrides the ``partitioner(keys) % axis_size``
    destination map entirely — the hook the salted skew path uses to
    spread a hot key over several shards (:func:`salted_dest`).
    """
    cap_in = part.capacity
    capacity = capacity or cap_in
    if dest is None:
        dest = (partitioner(keys) % jnp.uint32(axis_size)).astype(jnp.int32)
    valid = part.mask()
    pack = _pack_by_dest(part.records, dest, valid, axis_size, capacity)
    buf, send_counts, dropped = pack.buffer, pack.counts, pack.dropped
    recv = jax.tree.map(
        lambda l: jax.lax.all_to_all(
            l, axis_name, split_axis=0, concat_axis=0, tiled=False),
        buf)
    # recv leaf shape: [axis_size, capacity, ...] — row s = from source s.
    recv_counts = jax.lax.all_to_all(
        send_counts.reshape(axis_size, 1), axis_name,
        split_axis=0, concat_axis=0).reshape(axis_size)
    # Compact: valid slots are the first recv_counts[s] of each source row.
    slot_valid = (jnp.arange(capacity)[None, :] <
                  recv_counts[:, None]).reshape(-1)
    order = jnp.argsort(~slot_valid, stable=True)

    def compact(leaf):
        flat = leaf.reshape((axis_size * capacity,) + leaf.shape[2:])
        return jnp.take(flat, order, axis=0, mode="clip")

    out = make_partition(jax.tree.map(compact, recv),
                         jnp.sum(recv_counts).astype(jnp.int32))
    return ShuffleResult(part=out, dropped=dropped, send_counts=send_counts)


def grouped_all_to_all(
    x: jax.Array,
    group_ids: jax.Array,
    axis_name: str,
    axis_size: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array]:
    """Structured variant used by MoE dispatch: rows of ``x`` [tokens, d] are
    routed to shard ``group_ids[i] % axis_size`` keeping the [source, slot]
    structure (no compaction).  Returns (recv [axis_size, capacity, d],
    recv_counts [axis_size]).  This is repartitionBy with an identity
    partitioner — the chromosome-wise grouping of Listing 3, re-used as
    expert dispatch (DESIGN.md §3.2).
    """
    part = make_partition((x,), jnp.int32(x.shape[0]))
    dest = (group_ids % axis_size).astype(jnp.int32)
    pack = _pack_by_dest(part.records, dest, part.mask(), axis_size,
                         capacity)
    recv = jax.lax.all_to_all(pack.buffer[0], axis_name, split_axis=0,
                              concat_axis=0)
    recv_counts = jax.lax.all_to_all(
        pack.counts.reshape(axis_size, 1), axis_name,
        split_axis=0, concat_axis=0).reshape(axis_size)
    return recv, recv_counts
