"""Whole-pipeline lowering: a stage DAG compiled to ONE shard_map program.

MaRe's headline advantage over workflow engines is locality and
interactive processing: a ``map -> repartitionBy -> map -> reduce`` chain
should execute as one locality-preserving job, not as a sequence of
independently launched stages (the DAG-vs-Hadoop lesson of the MapReduce
survey literature).  The planner delivers that on JAX:

* :func:`lower` turns a :class:`~repro.core.plan.Plan` into a single
  shard-interior function — map chains feed straight into their downstream
  shuffle/reduce with no intermediate ``ShardedDataset`` materialization.
* Shuffle overflow counters become **outputs of the same program** (one
  ``[num_shuffles]`` vector per shard) instead of a host sync per shuffle;
  the driver checks them once, after the single dispatch.
* Compiled programs are memoized in a :class:`PlanCache` keyed on
  (stage structure, record shapes/dtypes, mesh, axis), so re-running an
  identical pipeline — the paper's Fig. 6 interactive workflow, or every
  wave of an out-of-core run — pays zero re-trace and zero re-compile.

This module is *lowering only*: actually dispatching a program, syncing
its counters and recording diagnostics is the runtime layer's job
(:mod:`repro.runtime.executor`, which also reuses materialized plan
prefixes via the lineage cache).
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.obs import METRICS, op_scopes, timed
from repro.core.container import Partition, make_partition
from repro.core.dataset import ShardedDataset
from repro.core.plan import (KeyedReduceStage, MapStage, Plan, ReduceStage,
                             ShuffleStage, _apply_chain, _IdKey)
from repro.core.shuffle import (hash_key_words, keyed_bucket_capacity,
                                salted_dest, shuffle_partition)
from repro.core.tree_reduce import (keyed_combine_partition,
                                    keyed_merge_partition,
                                    tree_reduce_partition)
from repro.kernels.segment_reduce.sort_agg import sort_aggregate


#: A MaRe scope in an op's ``op_name`` metadata: the stage's
#: ``s<i>.<kind>`` (:func:`lower`) and, in a keyed stage, its part
#: (:func:`_apply_keyed`).
SCOPE_PATTERN = re.compile(
    r"(?:^|/)(s\d+\.[a-z_]+(?:/(?:combine|exchange|merge))?)(?=/|$)")


def _stable(value: Any) -> str:
    """Text of a plan signature that every process renders alike:
    callables by qualified name, identity-keyed params by type."""
    if isinstance(value, tuple):
        return "(" + ",".join(_stable(v) for v in value) + ")"
    if isinstance(value, _IdKey):
        return type(value.obj).__name__
    if callable(value):
        return (f"{getattr(value, '__module__', '')}."
                f"{getattr(value, '__qualname__', type(value).__name__)}")
    return repr(value)


def program_name(plan: Plan) -> str:
    """The jit name of ``plan``'s program, e.g.
    ``mare_kmer_stats_reduce_by_key_3f2a07``: the images and commands of
    its map and reduce stages and the kinds of the others, in
    ``[A-Za-z0-9_]`` and at most 48 characters, then six hex digits of a
    SHA-1 over ``plan.describe()`` and the plan's signature (callables
    by qualified name).  The same plan gets the same name in every
    process; plans that differ in any op, command or parameter get
    different digests."""
    words = []
    for st in plan.stages:
        if isinstance(st, MapStage):
            ops = st.ops
        else:
            words.append(st.kind)
            ops = (st.op,) if isinstance(st, ReduceStage) else ()
        for op in ops:
            words.append(op.image)
            if op.command not in ("", op.image):
                words.append(op.command)
    stem = re.sub(r"[^A-Za-z0-9]+", "_", " ".join(words)).strip("_")
    text = plan.describe() + "|" + _stable(plan.signature())
    digest = hashlib.sha1(text.encode()).hexdigest()[:6]
    return "_".join(w for w in ("mare", stem[:48].rstrip("_"), digest) if w)


@dataclasses.dataclass
class CompiledProgram:
    """A jitted whole-pipeline shard_map program plus its plan metadata."""

    fn: Callable[..., Tuple]      # (records, counts) -> outputs
    counters: Tuple[Tuple[int, str], ...]  # trailing counter-vector layout
    key: Hashable                 # cache key it was compiled under
    #: :func:`program_name` of the plan: the jit name, so the compiled
    #: module (and a profile's ``XLA Modules`` events) is ``jit_<name>``.
    name: str
    #: :func:`local_keyed_stages` of the plan on its mesh: which keyed
    #: stages were lowered for one device (``report().diagnostics``
    #: ``stage<i>.local_keyed``).
    local_keyed: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: :func:`sorted_keyed_stages` of the plan: which keyed stages fold a
    #: two-word key by sorting (``stage<i>.sorted_keyed``).
    sorted_keyed: Dict[int, int] = dataclasses.field(default_factory=dict)
    _aot: Optional[Callable[..., Tuple]] = None   # jax.stages.Compiled
    _scopes: Optional[Dict[str, str]] = None

    def __call__(self, records: Any, counts: jax.Array) -> Tuple:
        if self._aot is not None:
            return self._aot(records, counts)
        return self.fn(records, counts)

    def as_text(self) -> str:
        """The compiled program's HLO text (after :meth:`ensure_compiled`)
        — e.g. to check that a Pallas kernel lowered to a
        ``tpu_custom_call``."""
        if self._aot is None:
            raise RuntimeError("program not compiled yet")
        return self._aot.as_text()

    def op_scopes(self) -> Dict[str, str]:
        """``{HLO instruction name: scope path}`` of the compiled program,
        e.g. ``{"fusion.2": "s0.map", "sort.38":
        "s1.reduce_by_key/combine"}``: which stage (and which part of a
        keyed stage) each device op of a profile ran for.  Ops outside
        every stage scope map to ``unscoped``; a fusion that spans two
        scopes counts under its root's (:func:`repro.obs.op_scopes`).
        Parsed from :meth:`as_text` on first call, then kept; never on
        the dispatch path."""
        if self._scopes is None:
            self._scopes = op_scopes(self.as_text(), SCOPE_PATTERN)
        return self._scopes

    @property
    def num_counters(self) -> int:
        return len(self.counters)

    def ensure_compiled(self, records: Any, counts: jax.Array,
                        phases: Optional[Dict[str, float]] = None) -> None:
        """AOT trace+compile against concrete arguments, once, so the
        executor can attribute lowering vs XLA-compile time as separate
        phases/spans instead of folding both into the first dispatch.

        The compiled executable is reused for every later dispatch (the
        plan cache keys on shapes/dtypes/mesh, so one signature per
        program).  Lowering and compile errors propagate: a program the
        backend refuses is a fault, not a reason to re-jit lazily.
        """
        if self._aot is not None:
            return
        args = ({name: {f"s{i}": v for i, v in stages.items()}
                 for name, stages in (("local_keyed", self.local_keyed),
                                      ("sorted_keyed", self.sorted_keyed))}
                if self.local_keyed else {})
        with timed("plan.lower", phases, **args):
            lowered = self.fn.lower(records, counts)
        with timed("plan.compile", phases):
            self._aot = lowered.compile()


class PlanCache:
    """Compile cache: pipeline shape -> :class:`CompiledProgram` (LRU).

    ``misses`` counts programs traced+compiled; ``hits`` counts reuses.
    The jitted callable is reused by object identity, so JAX's own jit
    cache is hit too — a cache hit implies zero re-trace.  ``maxsize``
    bounds retained programs (keys pin jitted executables and, for
    shuffle stages, the ``key_by`` callable — unbounded growth would be
    a leak in long interactive sessions with churning pipeline shapes).
    """

    def __init__(self, maxsize: int = 128) -> None:
        self._programs: "OrderedDict[Hashable, CompiledProgram]" = \
            OrderedDict()
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._programs)

    def stats(self) -> Dict[str, int]:
        return {"programs": len(self._programs), "hits": self.hits,
                "misses": self.misses}

    def programs(self) -> List[CompiledProgram]:
        """Retained programs, least recently used first."""
        return list(self._programs.values())

    def clear(self) -> None:
        self._programs.clear()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_compile(self, key: Hashable,
                       build: Callable[[], CompiledProgram],
                       phases: Optional[Dict[str, float]] = None
                       ) -> CompiledProgram:
        prog = self._programs.get(key)
        if prog is not None:
            self.hits += 1
            METRICS.counter("compile_cache.hits").inc()
            self._programs.move_to_end(key)
            return prog
        self.misses += 1
        METRICS.counter("compile_cache.misses").inc()
        with timed("plan.build", phases):
            prog = build()
        self._programs[key] = prog
        while len(self._programs) > self.maxsize:
            self._programs.popitem(last=False)
            self.evictions += 1
            METRICS.counter("compile_cache.evictions").inc()
        return prog


#: Process-wide default cache (MaRe actions and WaveRunner waves share it,
#: so a wave pipeline compiles once and amortizes across all waves).
DEFAULT_CACHE = PlanCache()


def program_key(plan: Plan, ds: ShardedDataset) -> Hashable:
    """Cache key: stage structure x input shapes/dtypes x mesh geometry."""
    leaves, treedef = jax.tree.flatten(ds.records)
    shapes = tuple((leaf.shape, str(leaf.dtype)) for leaf in leaves)
    return (plan.signature(), treedef, shapes,
            (tuple(ds.counts.shape), str(ds.counts.dtype)),
            ds.mesh, ds.axis)


def _apply_keyed(stage: KeyedReduceStage, part: Partition, axis: str,
                 axis_size: int) -> Tuple[Partition, List[jax.Array]]:
    """Shard-interior keyed aggregation: (combine) -> exchange -> merge.

    With the map-side combiner on, each shard first folds its records into
    at most ``num_keys`` per-key partials, so the exchange moves distinct
    keys, not records, and the per-destination send capacity is the
    statically known largest hash bucket (exact-lossless).  Combiner off
    ships raw ``(key, value, 1)`` records with the input capacity — the
    shuffle-volume baseline benchmarks compare against.

    Skew (``combiner=False, salt > 1``): a hot key makes the single-hop
    exchange degenerate — static SPMD forces ONE capacity for every
    (source, dest) pair, and a 90%-hot key forces it towards the full
    input capacity.  The salted path exchanges twice: hop 1 spreads each
    key's records over ``salt`` consecutive shards (``salted_dest``) at
    capacity ``~2 * cap_in / spread`` where ``spread = min(salt,
    axis_size)`` (a key cannot land on more destinations than exist),
    every shard merges what it received into per-key partials, and hop 2
    re-exchanges those partials combiner-style at the exact-lossless
    bucket capacity.  Buffer volume drops from ``axis_size * cap_in`` to
    ``axis_size * (2 * cap_in / spread + bucket_cap)`` rows per shard.  Hop 1's capacity is heuristic (2x
    headroom over the perfectly-spread hot key); adversarial key mixes
    can still overflow, which raises at action time with the
    ``max_send_count`` diagnostic as the tight retry capacity.

    One device (``axis_size == 1``, :func:`local_keyed_stages`): the
    exchange would only deliver to the shard itself the first ``cap``
    records it sends, in their order, so it is not lowered; no hash, no
    bucketing sort, no buffers.  The combiner's table is then already
    the answer (one record per key, ascending, capacity ``num_keys``),
    so no merge runs either: a merge of distinct keys would only fold
    each record into the identity and compact the table again.  With
    ``combiner=False`` one merge remains, the only fold; the salted
    second hop and its merge would refold a folded table, so they go
    too.  The counters still read what the exchange would have:
    ``shuffle_dropped`` is ``max(sent - cap, 0)`` (a too-small
    ``capacity=`` still raises), ``exchanged_records`` and
    ``max_send_count`` what crosses to the shard itself (hop 2's table
    included when salted), ``exchange_buffer_rows`` the buffer rows the
    exchange would allocate.

    Named scopes (``op_scopes``): ``combine`` (key and value selection,
    the map-side combiner or the compaction), ``exchange`` (bucketing,
    the all-to-all and its counters; both hops when salted) and
    ``merge`` (the post-exchange segment reduce; both merges).  On one
    device there is no ``exchange`` scope, and no ``merge`` scope with
    the combiner on.

    Counters (order = ``stage_counter_kinds``): key_overflow,
    shuffle_dropped, exchanged_records, max_send_count (max per-dest send
    this shard; max-reduced across shards by the executor),
    exchange_buffer_rows (static per-shard buffer allocation).
    """
    if stage.sorted:
        return _apply_sorted_keyed(stage, part, axis, axis_size)
    num_keys = stage.num_keys
    salt = 1 if stage.combiner else max(1, int(stage.salt))
    with jax.named_scope("combine"):
        keys = jnp.asarray(stage.key_by(part.records)).astype(jnp.int32)
        values = (stage.value_by(part.records) if stage.value_by is not None
                  else part.records)
        valid = part.mask()
        if stage.combiner:
            send, overflow = keyed_combine_partition(
                keys, values, valid, num_keys, op=stage.op,
                use_kernel=stage.use_kernel)
            default_cap = keyed_bucket_capacity(num_keys, axis_size)
        else:
            in_range = (keys >= 0) & (keys < num_keys)
            ok = valid & in_range
            overflow = jnp.sum(valid & ~in_range).astype(jnp.int32)
            # compact surviving records to the front (count semantics)
            order = jnp.argsort(~ok, stable=True)
            recs = (jnp.take(keys, order, mode="clip"),
                    jax.tree.map(lambda l: jnp.take(l, order, axis=0,
                                                    mode="clip"), values),
                    jnp.take(ok.astype(jnp.int32), order, mode="clip"))
            send = make_partition(recs, jnp.sum(ok).astype(jnp.int32))
            if salt > 1:
                # perfectly-spread hot key needs cap_in/spread; 2x headroom
                # for overlapping salt windows of distinct keys. A key can
                # never spread over more destinations than exist, so the
                # spread factor is capped at axis_size (salt > axis_size on
                # a small mesh must not shrink the buffer below what one
                # destination can receive).
                spread = min(salt, axis_size)
                default_cap = min(part.capacity,
                                  2 * ((part.capacity + spread - 1) // spread))
            else:
                default_cap = part.capacity  # any shard may ship every record
    cap = stage.capacity or default_cap
    if axis_size == 1:
        return _apply_keyed_local(stage, send, overflow, cap, salt)
    with jax.named_scope("exchange"):
        dest = (salted_dest(send.records[0], axis_size, salt)
                if salt > 1 else None)
        res = shuffle_partition(send, send.records[0], axis_name=axis,
                                axis_size=axis_size, capacity=cap,
                                dest=dest)
        exchanged = jnp.sum(res.send_counts).astype(jnp.int32)
        max_send = jnp.max(res.send_counts).astype(jnp.int32)
    buffer_rows = axis_size * cap
    with jax.named_scope("merge"):
        out, merge_overflow = keyed_merge_partition(
            res.part, num_keys, op=stage.op, use_kernel=stage.use_kernel)
    dropped = res.dropped
    if salt > 1:
        # hop 2: per-key partials back to their hash owner (combiner-style,
        # exact-lossless capacity) + final merge
        cap2 = keyed_bucket_capacity(num_keys, axis_size)
        with jax.named_scope("exchange"):
            res2 = shuffle_partition(out, out.records[0], axis_name=axis,
                                     axis_size=axis_size, capacity=cap2)
            exchanged = exchanged + jnp.sum(res2.send_counts).astype(
                jnp.int32)
            max_send = jnp.maximum(
                max_send, jnp.max(res2.send_counts).astype(jnp.int32))
        buffer_rows += axis_size * cap2
        dropped = dropped + res2.dropped
        with jax.named_scope("merge"):
            out, merge2_overflow = keyed_merge_partition(
                res2.part, num_keys, op=stage.op,
                use_kernel=stage.use_kernel)
        merge_overflow = merge_overflow + merge2_overflow
    return out, [(overflow + merge_overflow).astype(jnp.int32),
                 dropped.astype(jnp.int32), exchanged, max_send,
                 jnp.full((), buffer_rows, jnp.int32)]


def _apply_keyed_local(stage: KeyedReduceStage, send: Partition,
                       overflow: jax.Array, cap: int, salt: int
                       ) -> Tuple[Partition, List[jax.Array]]:
    """:func:`_apply_keyed` after its ``combine`` scope, on one device:
    the shard "receives" the first ``cap`` records of ``send``, and
    only a stage without the combiner merges them."""
    num_keys = stage.num_keys
    sent = jnp.minimum(send.count, cap).astype(jnp.int32)
    dropped = send.count - sent
    exchanged = max_send = sent
    buffer_rows = cap
    out = make_partition(send.records, sent)
    if not stage.combiner:
        with jax.named_scope("merge"):
            out, merge_overflow = keyed_merge_partition(
                out, num_keys, op=stage.op, use_kernel=stage.use_kernel)
        overflow = overflow + merge_overflow
        if salt > 1:
            # hop 2 would send the merged table to its one owner: no
            # more records than hop 1 sent, so max_send stands
            exchanged = exchanged + out.count
            buffer_rows += keyed_bucket_capacity(num_keys, 1)
    return out, [overflow.astype(jnp.int32), dropped.astype(jnp.int32),
                 exchanged, max_send, jnp.full((), buffer_rows, jnp.int32)]


def _apply_sorted_keyed(stage: KeyedReduceStage, part: Partition, axis: str,
                        axis_size: int) -> Tuple[Partition, List[jax.Array]]:
    """Shard-interior sorted keyed stage (a two-word key, no table).

    ``combine``: the records, with a count of 1 each, are summed per
    distinct key by :func:`~repro.kernels.segment_reduce.sort_agg.
    sort_aggregate` (on a mesh only with the combiner on; on one device
    always, as the stage's one fold).  On a mesh, ``exchange`` sends each
    record or partial to the owner that :func:`~repro.core.shuffle.
    hash_key_words` picks, at the shard's record capacity (``capacity=``
    if given), so no hot key can overflow it, and ``merge`` folds what
    arrived, partial sums and counts alike.  One device lowers neither
    (:func:`_apply_keyed_local`'s reasoning).  The output is one record
    ``(key [2], values, count)`` a distinct key, ascending, compacted to
    the front.

    Counters (order = ``stage_counter_kinds``): shuffle_dropped,
    exchanged_records, max_send_count, exchange_buffer_rows and
    distinct_keys (the records this shard output).
    """
    with jax.named_scope("combine"):
        keys = stage.key_by(part.records)
        values = (stage.value_by(part.records) if stage.value_by is not None
                  else part.records)
        valid = part.mask()
        ones = valid.astype(jnp.int32)
        if stage.combiner or axis_size == 1:
            agg = sort_aggregate(keys, values, ones, valid)
            send = make_partition((agg.keys, agg.values, agg.counts),
                                  agg.distinct)
        else:
            send = make_partition((keys, values, ones), part.count)
    cap = stage.capacity or send.capacity
    if axis_size == 1:
        sent = jnp.minimum(send.count, cap).astype(jnp.int32)
        out = make_partition(send.records, sent)
        return out, [(send.count - sent).astype(jnp.int32), sent, sent,
                     jnp.full((), cap, jnp.int32), sent]
    with jax.named_scope("exchange"):
        dest = (hash_key_words(send.records[0])
                % jnp.uint32(axis_size)).astype(jnp.int32)
        res = shuffle_partition(send, send.records[0], axis_name=axis,
                                axis_size=axis_size, capacity=cap,
                                dest=dest)
        exchanged = jnp.sum(res.send_counts).astype(jnp.int32)
        max_send = jnp.max(res.send_counts).astype(jnp.int32)
    with jax.named_scope("merge"):
        rkeys, rvalues, rcounts = res.part.records
        agg = sort_aggregate(rkeys, rvalues, rcounts, res.part.mask())
        out = make_partition((agg.keys, agg.values, agg.counts),
                             agg.distinct)
    return out, [res.dropped.astype(jnp.int32), exchanged, max_send,
                 jnp.full((), axis_size * cap, jnp.int32), out.count]


def sorted_keyed_stages(plan: Plan) -> Dict[int, int]:
    """``{i: 1 or 0}`` for each keyed stage ``i`` of ``plan``: 1 where it
    folds a two-word key by sorting (:func:`_apply_sorted_keyed`)."""
    return {i: int(st.sorted) for i, st in enumerate(plan.stages)
            if isinstance(st, KeyedReduceStage)}


def local_keyed_stages(plan: Plan, axis_size: int) -> Dict[int, int]:
    """``{i: 1 or 0}`` for each keyed stage ``i`` of ``plan``: 1 where
    :func:`_apply_keyed` lowers it without the exchange, which is where
    the mesh axis has one device."""
    return {i: int(axis_size == 1) for i, st in enumerate(plan.stages)
            if isinstance(st, KeyedReduceStage)}


def _validate_mount(mount, records, stage_idx: int, op_name: str,
                    which: str) -> None:
    """Execution-time mount validation with stage/image context (fires
    when plan-time inference couldn't check — unknown upstream schema)."""
    if mount is None:
        return
    try:
        mount.validate(records)
    except ValueError as e:
        raise ValueError(
            f"stage {stage_idx} (reduce[{op_name}]): {which} mount "
            f"validation failed: {e}") from e


def _apply_stage(stage, part: Partition, axis: str, axis_size: int,
                 stage_idx: int = 0
                 ) -> Tuple[Partition, List[jax.Array]]:
    """Shard-interior application of one stage; returns ``(part,
    counters)`` with counters matching ``stage_counter_kinds(stage)``."""
    if isinstance(stage, MapStage):
        return _apply_chain(stage.ops, part.records, part.count,
                            stage_idx), []
    if isinstance(stage, ShuffleStage):
        keys = stage.key_by(part.records)
        if (stage.num_partitions is not None
                and stage.num_partitions != axis_size):
            keys = keys % stage.num_partitions
        res = shuffle_partition(part, keys, axis_name=axis,
                                axis_size=axis_size,
                                capacity=stage.capacity)
        return res.part, [res.dropped.astype(jnp.int32)]
    if isinstance(stage, KeyedReduceStage):
        return _apply_keyed(stage, part, axis, axis_size)
    if isinstance(stage, ReduceStage):
        _validate_mount(stage.op.input_mount, part.records, stage_idx,
                        stage.op.name, "input")
        part = tree_reduce_partition(
            part, stage.op, axis_name=axis, axis_size=axis_size,
            depth=stage.depth)
        _validate_mount(stage.op.output_mount, part.records, stage_idx,
                        stage.op.name, "output")
        return part, []
    raise TypeError(f"unknown stage type {type(stage).__name__}")


def lower(plan: Plan, axis: str, axis_size: int):
    """Build the shard-interior function for a whole plan.

    Returns ``interior(records, counts) -> (records, counts[, counters])``
    where ``counters`` is an int32 vector laid out per
    ``plan.counter_specs()`` (omitted when the plan has none): shuffle
    drop counts, keyed-reduce key-table overflow, exchanged-record volume.
    Stage ``i`` runs under ``jax.named_scope("s<i>.<kind>")`` (e.g.
    ``s0.map``, ``s1.reduce_by_key``): metadata only, the compiled ops
    are the same.
    """

    def interior(records, counts):
        part = make_partition(records, counts[0])
        counters: List[jax.Array] = []
        for i, stage in enumerate(plan.stages):
            with jax.named_scope(f"s{i}.{stage.kind}"):
                part, cs = _apply_stage(stage, part, axis, axis_size, i)
            counters.extend(cs)
        outs = (part.records, part.count[None])
        if counters:
            outs = outs + (jnp.stack(counters).astype(jnp.int32),)
        return outs

    return interior


def _plan_uses_pallas(plan: Plan) -> bool:
    """Whether any keyed stage COULD resolve to the Pallas segment-reduce
    kernel (shard_map has no replication rule for pallas_call, so such a
    program must be built with the replication check off).  Conservative:
    with ``use_kernel=None`` the autotuner decides at trace time, so this
    answers "is tiled in the candidate set" (TPU backend or a forced
    kernel), not "will tiled win"."""
    from repro.kernels.segment_reduce.ops import resolve_use_kernel
    return any(isinstance(st, KeyedReduceStage) and not st.sorted
               and resolve_use_kernel(st.use_kernel, st.op)
               for st in plan.stages)


def compile_plan(plan: Plan, ds: ShardedDataset,
                 cache: Optional[PlanCache] = None,
                 phases: Optional[Dict[str, float]] = None
                 ) -> CompiledProgram:
    """Memoized lowering of ``plan`` against ``ds``'s shapes and mesh,
    jitted under :func:`program_name`.  ``phases`` (when given)
    accumulates build time under ``plan.build``."""
    cache = cache if cache is not None else DEFAULT_CACHE
    mesh, axis = ds.mesh, ds.axis
    key = program_key(plan, ds)

    def build() -> CompiledProgram:
        counters = plan.counter_specs()
        name = program_name(plan)
        axis_size = int(mesh.shape[axis])
        interior = lower(plan, axis, axis_size)
        interior.__name__ = interior.__qualname__ = name
        out_specs = (P(axis), P(axis)) + ((P(axis),) if counters else ())
        check_vma = False if _plan_uses_pallas(plan) else None
        fn = jax.jit(compat.shard_map(
            interior, mesh=mesh, in_specs=(P(axis), P(axis)),
            out_specs=out_specs, check_vma=check_vma))
        return CompiledProgram(fn=fn, counters=counters, key=key,
                               name=name,
                               local_keyed=local_keyed_stages(plan,
                                                              axis_size),
                               sorted_keyed=sorted_keyed_stages(plan))

    return cache.get_or_compile(key, build, phases=phases)


# NOTE: action execution (dispatch, counter sync, prefix-cache reuse,
# per-action reports) lives in repro.runtime.executor — this module stops
# at lowering + program memoization.  ``repro.runtime.execute`` is the
# bare dispatch engine; ``repro.runtime.Executor`` the full one.
