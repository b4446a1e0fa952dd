"""MaRe: the user-facing driver API (paper Listings 1-3, JAX edition).

.. code-block:: python

    result = (MaRe(dataset)
        .map(input_mount=TextFile("/dna"), output_mount=TextFile("/count"),
             image="posix", command="grep -c [GC]")
        .reduce(input_mount=TextFile("/counts"),
                output_mount=TextFile("/sum"),
                image="posix", command="awk-sum")
        .collect())

Semantics match the paper: ``map`` applies a container to each partition
(single stage, no shuffle); ``reduce`` aggregates all partitions down to one
via a depth-K tree (K shuffles, combiner must be associative+commutative;
default K=2); ``repartition_by`` co-locates records by key (hash shuffle).
Ops are pulled from the registry by image name; a ``command`` string is
passed to the image factory (images interpret their own command grammar,
like a container ENTRYPOINT).

All primitives are **lazy**: they append stages to a logical plan.  MaRe
itself is a thin facade — an action (``collect`` / ``persist`` /
``dataset``) hands the chain to the runtime layer
(:mod:`repro.runtime`): the planner lowers it into a single memoized
``shard_map`` program, and the executor dispatches it, reusing any plan
*prefix* previously materialized with :meth:`MaRe.persist`
(lineage-keyed cache), syncing stage counters once, and appending an
:class:`~repro.runtime.reports.ActionReport` to the shared per-chain
history (:meth:`MaRe.report` / :meth:`MaRe.reports`).

There is ONE action signature: ``collect(shard=..., asynchronous=...,
label=...)``.  The former variants (``collect_async``,
``collect_first_shard``, ``collect_first_shard_async``) and the
``last_diagnostics`` dict survive as deprecated shims, as do the
paper-spelling camelCase aliases (``repartitionBy``, ``reduceByKey``,
``inputMountPoint=`` / ``outputMountPoint=``) — all centralized in
:data:`PAPER_METHOD_ALIASES` / :data:`PAPER_KWARG_ALIASES` and applied
by the :func:`paper_aliases` class decorator, each warning once per
process (:mod:`repro.deprecations`).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, TYPE_CHECKING

import jax
from jax.sharding import Mesh

from repro import compat
from repro.core import dataset as ds_lib
from repro.core import planner as planner_lib
from repro.core.container import (ContainerOp, Registry, DEFAULT_REGISTRY)
from repro.core.dataset import ShardedDataset
from repro.core.mounts import Mount
from repro.core.plan import (KEYED_MONOIDS, Plan, StageState, infer_stage,
                             infer_states, key_words)
from repro.core.schema import schema_of_records
from repro.deprecations import warn_once

if TYPE_CHECKING:  # runtime imported lazily: core must not require
    from repro.runtime.executor import ActionHandle, Executor  # noqa: F401
    from repro.runtime.reports import ActionReport, ReportLog  # noqa: F401


#: Deprecated camelCase method -> canonical snake_case method, applied to
#: MaRe by :func:`paper_aliases` (the ONE place paper spellings live).
PAPER_METHOD_ALIASES: Dict[str, str] = {
    "repartitionBy": "repartition_by",
    "reduceByKey": "reduce_by_key",
}

#: Deprecated camelCase kwarg -> canonical kwarg, translated on the
#: methods listed in :data:`PAPER_KWARG_METHODS`.
PAPER_KWARG_ALIASES: Dict[str, str] = {
    "inputMountPoint": "input_mount",
    "outputMountPoint": "output_mount",
}

#: Methods whose kwargs go through the alias table.
PAPER_KWARG_METHODS = ("map", "reduce")


def _alias_method(camel: str, snake: str) -> Callable:
    def shim(self, *args: Any, **kwargs: Any):
        warn_once(("method", camel),
                  f"MaRe.{camel}() is deprecated; use MaRe.{snake}() "
                  f"(paper-spelling alias, forwarded unchanged)")
        return getattr(self, snake)(*args, **kwargs)

    shim.__name__ = camel
    shim.__qualname__ = f"MaRe.{camel}"
    shim.__doc__ = (f"Deprecated paper spelling of :meth:`{snake}` "
                    f"(warns once, forwards everything).")
    return shim


def _translate_kwargs(fn: Callable) -> Callable:
    @functools.wraps(fn)
    def wrapper(self, *args: Any, **kwargs: Any):
        for camel, snake in PAPER_KWARG_ALIASES.items():
            if camel in kwargs:
                if kwargs.get(snake) is not None:
                    raise TypeError(
                        f"{fn.__name__}() got both {snake!r} and its "
                        f"deprecated alias {camel!r}")
                warn_once(("kwarg", camel),
                          f"{camel}= is deprecated; use {snake}= "
                          f"(paper-spelling kwarg alias)")
                kwargs[snake] = kwargs.pop(camel)
        return fn(self, *args, **kwargs)

    return wrapper


def paper_aliases(cls):
    """Class decorator installing every paper-spelling alias from the
    tables above — ad-hoc per-method aliasing is not allowed; add new
    spellings to the tables instead."""
    for camel, snake in PAPER_METHOD_ALIASES.items():
        setattr(cls, camel, _alias_method(camel, snake))
    for name in PAPER_KWARG_METHODS:
        setattr(cls, name, _translate_kwargs(getattr(cls, name)))
    return cls


#: Per-shard finalizer cache: ``collect(shard=i)`` must hand the runtime
#: the SAME callable object for a given ``i`` every time — finalize
#: identity is part of the cross-session batch key, so two sessions
#: asking for shard 0 of the same lineage coalesce into one dispatch.
_SHARD_FINALIZERS: Dict[int, Callable] = {}


def _finalizer(shard: Optional[int]) -> Callable:
    """The dataset->host callable for ``collect(shard=...)``: whole-dataset
    gather when ``shard`` is None, else a cached per-shard slicer."""
    if shard is None:
        return ds_lib.collect
    fn = _SHARD_FINALIZERS.get(shard)
    if fn is None:
        fn = _SHARD_FINALIZERS[shard] = functools.partial(
            ds_lib.collect_shard, shard=shard)
    return fn


def _resolve_monoid(image: str, command: str, registry: Registry) -> str:
    """Keyed-reduce combiner via the paper's container spelling: the image
    is pulled and its *manifest* must declare a monoid (``toolbox/sum``
    and the posix ``awk-sum`` command declare ``monoid="sum"``)."""
    op = registry.pull(image, command=command)
    monoid = op.contract.monoid if op.contract is not None else None
    if monoid is None:
        raise ValueError(
            f"image {image!r} (command {command!r}) is not a known "
            f"keyed-reduce monoid: its manifest declares no `monoid`; "
            f"use op= directly ({KEYED_MONOIDS}) or an image whose "
            f"manifest declares one (e.g. 'toolbox/sum', or 'ubuntu' "
            f"with command 'awk-sum')")
    return monoid


def _resolve_op(image: Optional[str], op: Optional[ContainerOp],
                command: str, registry: Registry,
                input_mount: Optional[Mount],
                output_mount: Optional[Mount], **params: Any) -> ContainerOp:
    if op is None:
        if image is None:
            raise ValueError("either `image` or `op` must be given")
        op = registry.pull(image, command=command, **params)
    if input_mount is not None or output_mount is not None:
        op = op.with_mounts(input_mount, output_mount, command)
    return op


@paper_aliases
class MaRe:
    """Driver handle over a :class:`ShardedDataset` with a lazy stage plan.

    ``plan_cache`` overrides the process-wide compile cache (mostly for
    tests/benchmarks); ``fuse=False`` forces stage-at-a-time execution
    (each stage its own program — the pre-planner schedule); ``executor``
    overrides the process-wide runtime engine (its materialization cache
    is what ``persist()`` feeds).
    """

    def __init__(self, data: Any, mesh: Optional[Mesh] = None,
                 axis: str = "data",
                 registry: Registry = DEFAULT_REGISTRY,
                 _plan: Optional[Plan] = None,
                 plan_cache: Optional["planner_lib.PlanCache"] = None,
                 fuse: bool = True,
                 executor: Optional[Executor] = None,
                 _reports: Optional[ReportLog] = None):
        # deferred: repro.runtime depends on core submodules, so importing
        # it at core-module import time would be circular either way round
        from repro.runtime.executor import DEFAULT_EXECUTOR
        from repro.runtime.reports import ReportLog
        if isinstance(data, ShardedDataset):
            self._dataset = data
        else:
            if mesh is None:
                mesh = compat.make_mesh((jax.device_count(),), (axis,))
            self._dataset = ds_lib.from_host(data, mesh, axis)
        self.registry = registry
        self.plan = _plan or Plan()
        self.plan_cache = plan_cache
        self.fuse = fuse
        self.executor = executor if executor is not None else DEFAULT_EXECUTOR
        # Per-chain action history (shared across handles forked from this
        # one): every action appends an ActionReport here AND to the
        # executor's global history.  Surfaced via report()/reports().
        self._report_log = _reports if _reports is not None else ReportLog()
        #: Inferred StageState per stage boundary (build-time type check);
        #: computed in _chain, reset when the plan materializes.
        self._states: Optional[list] = None

    @classmethod
    def from_source(cls, source: Any, mesh: Optional[Mesh] = None,
                    axis: str = "data", capacity: Optional[int] = None,
                    width: Optional[int] = None,
                    workers: Optional[int] = None,
                    registry: Registry = DEFAULT_REGISTRY,
                    executor: Optional[Executor] = None,
                    parser: str = "vectorized") -> "MaRe":
        """Ingest a :class:`repro.io.DataSource` (storage backend + format
        + split plan) into a sharded dataset via the parallel fetch pool —
        the paper's heterogeneous-storage entry point (Fig. 5).
        ``parser`` selects the framing path: ``"vectorized"`` columnar
        :class:`~repro.io.formats.RecordBatch` (default) or the
        ``"legacy"`` per-line oracle it is property-tested against."""
        from repro.io.ingest import ingest  # deferred: io depends on core
        if mesh is None:
            mesh = compat.make_mesh((jax.device_count(),), (axis,))
        ds = ingest(source, mesh, axis=axis, capacity=capacity,
                    width=width, workers=workers, parser=parser)
        return cls(ds, registry=registry, executor=executor)

    # -- reports -------------------------------------------------------------

    def report(self) -> Optional["ActionReport"]:
        """The NEWEST :class:`~repro.runtime.reports.ActionReport` on this
        chain (None before the first action).  ``report().diagnostics``
        is the per-stage counter dict; ``report().phases`` the wall
        breakdown."""
        return self._report_log.latest

    def reports(self) -> "ReportLog":
        """The chain's full action history (shared across forked handles):
        a :class:`~repro.runtime.reports.ReportLog` — iterate, index,
        ``total(counter)``, ``summary()``."""
        return self._report_log

    @property
    def last_diagnostics(self) -> dict:
        """Deprecated: counter totals of the newest action.  Use
        ``report().diagnostics`` (and ``reports()`` for history)."""
        warn_once(("property", "last_diagnostics"),
                  "MaRe.last_diagnostics is deprecated; use "
                  "MaRe.report().diagnostics (reports() for history)")
        latest = self.report()
        return latest.diagnostics if latest is not None else {}

    def _initial_state(self) -> StageState:
        ds = self._dataset
        return StageState(schema=schema_of_records(ds.records),
                          capacity=ds.capacity, num_shards=ds.num_shards)

    def _stage_states(self) -> list:
        """Inferred [initial, after-stage-0, ...] states for the pending
        plan — the build-time type check (raises PlanTypeError)."""
        if self._states is None:
            self._states = infer_states(self.plan, self._initial_state())
        return self._states

    def _chain(self, plan: Plan) -> "MaRe":
        m = MaRe(self._dataset, registry=self.registry, _plan=plan,
                 plan_cache=self.plan_cache, fuse=self.fuse,
                 executor=self.executor, _reports=self._report_log)
        # type-check at BUILD time, incrementally: every primitive either
        # appends one stage or extends the trailing MapStage, so the
        # parent's inferred states are a valid prefix up to the new plan's
        # last stage — only that stage is (re-)inferred here, keeping
        # chain construction O(1) per call instead of O(stages).
        prefix = self._stage_states()[:len(plan.stages)]
        last = len(plan.stages) - 1
        m._states = prefix + [infer_stage(plan.stages[last], prefix[-1],
                                          last)]
        return m

    def _materialize(self, label: Optional[str] = None) -> ShardedDataset:
        """Run all pending stages through the runtime executor: one fused
        program for the suffix not already materialized in the lineage
        cache, one counter sync, one appended ActionReport."""
        if not self.plan.empty:
            self._dataset, _ = self.executor.run(
                self._dataset, self.plan, fuse=self.fuse,
                plan_cache=self.plan_cache, reports=self._report_log,
                label=label)
            self.plan = Plan()
            self._states = None
        else:
            self.executor.ensure_lineage(self._dataset)
        return self._dataset

    @property
    def dataset(self) -> ShardedDataset:
        """The materialized dataset (triggers execution of pending stages)."""
        return self._materialize()

    # -- primitives ---------------------------------------------------------

    def map(self, *, image: Optional[str] = None,
            op: Optional[ContainerOp] = None,
            command: str = "",
            input_mount: Optional[Mount] = None,
            output_mount: Optional[Mount] = None,
            **params: Any) -> "MaRe":
        """Apply a container to each partition (lazy; fused into one stage).

        The paper spelling (``inputMountPoint=`` / ``outputMountPoint=``)
        is accepted as a deprecated alias via :func:`paper_aliases`.
        """
        op = _resolve_op(image, op, command, self.registry,
                         input_mount, output_mount, **params)
        return self._chain(self.plan.then(op))

    def reduce(self, *, image: Optional[str] = None,
               op: Optional[ContainerOp] = None,
               command: str = "",
               input_mount: Optional[Mount] = None,
               output_mount: Optional[Mount] = None,
               depth: int = 2,
               **params: Any) -> "MaRe":
        """K-level tree aggregation of all partitions to one (paper K=2).

        Lazy: appends a reduce stage; the pending map chain, the reduce
        tree and any upstream shuffles run in a single ``shard_map``
        program at action time.  The result is replicated on every shard
        (single-partition RDD')."""
        op = _resolve_op(image, op, command, self.registry,
                         input_mount, output_mount, **params)
        if not op.associative_commutative:
            raise ValueError(
                f"reduce combiner {op.name} is not marked associative+"
                "commutative (paper: required for tree-reduce consistency)")
        return self._chain(self.plan.then_reduce(op, depth))

    def repartition_by(self, key_by: Callable[[Any], jax.Array],
                       capacity: Optional[int] = None,
                       num_partitions: Optional[int] = None) -> "MaRe":
        """Hash-shuffle records so equal keys share a partition (lazy).

        ``key_by(records) -> int array [capacity]`` (vectorized keyBy over
        the record pytree).  ``num_partitions`` other than the axis size is
        emulated by keying into ``num_partitions`` buckets spread over the
        axis (paper sets it to #workers, which is the axis size here).

        Capacity overflow (dropped records) raises ``RuntimeError`` at
        action time: the fused program returns per-shuffle drop counters
        as outputs, so a chain with K shuffles pays one host sync total
        instead of K.
        """
        return self._chain(self.plan.then_shuffle(
            key_by, capacity=capacity, num_partitions=num_partitions))

    def reduce_by_key(self, key_by: Callable[[Any], jax.Array], *,
                      num_keys: Optional[int] = None,
                      op: str = "sum",
                      value_by: Optional[Callable[[Any], Any]] = None,
                      image: Optional[str] = None,
                      command: str = "",
                      combiner: bool = True,
                      capacity: Optional[int] = None,
                      use_kernel: Optional[bool] = None,
                      salt: int = 1) -> "MaRe":
        """Grouped aggregation: fold records with equal keys (lazy).

        ``key_by(records)`` computes a key per record, and its form picks
        the stage, statically, at build time:

        * a one-word key (int ``[capacity]``) is **dense**: keys must lie
          in ``[0, num_keys)`` (the bounded key table — out-of-range keys
          raise ``RuntimeError`` at action time through the same one-sync
          error channel as shuffle overflow).  When the upstream image's
          manifest declares a ``key_space`` (e.g. ``kmer-stats``:
          ``4**k`` for ``k <= 15``), ``num_keys`` may be omitted and is
          inferred at plan time — and an explicit ``num_keys`` smaller
          than the declared key space fails at *build* time;
        * a two-word key (32-bit ints ``[capacity, 2]``, high then low,
          e.g. ``kmer-stats`` codes for ``16 <= k <= 31``) is **sorted**:
          there is no table and no ``num_keys`` (passing one is an
          error; it is for one-word keys only), and ``op`` is ``sum``
          over integer values.  Each shard sorts its records on the key
          and sums each run of equal keys
          (``repro.kernels.segment_reduce.sort_agg``); the output holds
          one record per distinct key, in ascending key order, within a
          capacity of the input records (times the shards on a mesh), and
          ``report().diagnostics['stage<i>.distinct_keys']`` counts them.
          On a mesh the records (or, with the combiner, each shard's
          partials) go to the owner that a hash of both words picks, at
          the shard's record capacity, so none can be dropped.

        ``value_by`` selects the value pytree to fold (default: the whole
        record pytree); ``op`` is the merge monoid (``sum`` / ``max`` /
        ``min``, associative+commutative by construction), or pass a
        container spelling (``image="toolbox/sum"``, or ``image="ubuntu",
        command="awk-sum"``) — the pulled image's *manifest* must declare
        the monoid, as in the paper's combiner listings.

        Execution fuses into the single program like every other stage:
        with ``combiner=True`` (default) each shard pre-aggregates per key
        **before** the hash exchange — the classic map-side combiner — so
        shuffle volume scales with distinct keys, not records, and a
        dense stage's per-destination send capacity is the
        statically-known largest hash bucket.  On one device there is no
        exchange, and a keyed stage folds once whatever ``combiner`` says.
        The result partition on each shard holds the keys hashing to it
        as records ``(key, folded_values, record_count)``, compacted to
        the front.  A dense stage's segment-reduce hot path autotunes
        between the tiled Pallas kernel and the fused/sorted/scatter jnp
        strategies per shape (``use_kernel=True/False`` forces the
        kernel/the plain scatter; ``REPRO_SEGMENT_KERNEL`` overrides the
        default; see docs/kernels.md).

        Skew (dense stages): with ``combiner=False`` a hot key inflates
        every shard's statically-sized exchange buffer.  ``salt=S`` (S >
        1) spreads each key's records over S consecutive shards and
        re-exchanges per-key partials in a second hop, shrinking buffers
        by ~S/2 on hot-key data (docs/architecture.md §keyed exchange).
        After any action, ``report().diagnostics['stage<i>.max_send_count']``
        is the tightest lossless ``capacity=`` observed — the feedback
        knob if the salted heuristic capacity ever overflows.  ``salt``
        with ``combiner=True`` is rejected: the combiner already bounds
        the exchange by distinct keys, so salting could only add a hop.
        """
        if image is not None:
            op = _resolve_monoid(image, command, self.registry)
        if op not in KEYED_MONOIDS:
            raise ValueError(f"unknown reduce_by_key op {op!r}; expected "
                             f"one of {KEYED_MONOIDS}")
        if salt < 1:
            raise ValueError(f"salt must be >= 1, got {salt}")
        if salt > 1 and combiner:
            raise ValueError(
                "salt > 1 requires combiner=False: the map-side combiner "
                "already caps the exchange at one record per distinct key, "
                "so hot-key splitting has nothing to spread")
        state = self._stage_states()[-1]
        if key_words(key_by, state) == 2:
            if num_keys is not None:
                raise ValueError(
                    f"num_keys={num_keys} given for a two-word key "
                    "([capacity, 2]): num_keys sizes the table of a "
                    "one-word key only; a two-word key takes the sorted "
                    "keyed stage, which needs none")
            if salt > 1:
                raise ValueError(
                    "salt > 1 applies to a dense (one-word) key only: the "
                    "sorted keyed stage exchanges at the shard's record "
                    "capacity, which no hot key can overflow")
            if op != "sum":
                raise ValueError(
                    f"op={op!r} over a two-word key: the sorted keyed "
                    "stage folds op='sum' of integer values only")
            return self._chain(self.plan.then_keyed_reduce(
                key_by, op=op, num_keys=None, value_by=value_by,
                combiner=combiner, capacity=capacity,
                use_kernel=use_kernel))
        if num_keys is None:
            num_keys = state.key_space
            if num_keys is None:
                raise ValueError(
                    "num_keys not given and no upstream image manifest "
                    "declares a key_space to infer it from")
        if num_keys < 1:
            raise ValueError(f"num_keys must be >= 1, got {num_keys}")
        return self._chain(self.plan.then_keyed_reduce(
            key_by, op=op, num_keys=num_keys, value_by=value_by,
            combiner=combiner, capacity=capacity, use_kernel=use_kernel,
            salt=salt))

    # -- actions ------------------------------------------------------------

    def persist(self, tier: str = "device") -> "MaRe":
        """Materialize the pending plan and register the result in the
        runtime's lineage-keyed materialization cache (Spark
        ``RDD.persist`` analogue).

        ``tier="device"`` keeps the sharded arrays live on the mesh;
        ``tier="host"`` stores a host copy that is re-placed on a hit.
        The cache is budgeted LRU per tier (device evictions spill to
        host, host evictions drop — recomputable from lineage).  After
        ``persist()``, ANY handle whose plan prefix reaches this lineage
        node — including forks of an ancestor handle rebuilding the same
        stages — starts from the cached dataset and executes only the
        suffix.
        """
        ds = self._materialize()
        self.executor.persist(ds, tier=tier)
        return MaRe(ds, registry=self.registry, plan_cache=self.plan_cache,
                    fuse=self.fuse, executor=self.executor,
                    _reports=self._report_log)

    def cache(self) -> "MaRe":
        """Sugar for :meth:`persist` (``tier="device"``).

        Pre-runtime, ``cache()`` was an eager materialize on one handle
        only; it now also registers the result under its lineage, so
        sibling handles sharing the prefix reuse it.
        """
        return self.persist(tier="device")

    def collect(self, *, shard: Optional[int] = None,
                asynchronous: bool = False,
                label: Optional[str] = None) -> Any:
        """THE action: run pending stages and gather valid records to host.

        ``shard=None`` gathers every shard's valid records
        (``RDD.collect``); ``shard=i`` slices one shard's block on device
        and ships only its valid rows — the right call for reduced
        (replicated) results, where ``shard=0`` replaces the old
        ``collect_first_shard``.

        ``asynchronous=False`` (default) blocks and returns host arrays.
        ``asynchronous=True`` dispatches on the executor's action thread
        behind its bounded queue and returns an
        :class:`~repro.runtime.executor.ActionHandle` (``.result()``
        blocks, ``.report`` carries the ActionReport).  Snapshot
        semantics: the handle's pending plan is captured at call time and
        this handle is left lazy (a later sync action on it re-resolves
        against the materialization cache — persist first if the prefix
        should be shared).

        ``label`` tags the action's report either way (e.g. ``"wave 3"``
        on the wave path, query names in interactive sessions).
        """
        if shard is not None and not (0 <= shard
                                      < self._dataset.num_shards):
            raise ValueError(
                f"shard index {shard} out of range for "
                f"{self._dataset.num_shards}-shard dataset")
        finalize = _finalizer(shard)
        if not asynchronous:
            return finalize(self._materialize(label=label))
        return self.executor.submit_action(
            self._dataset, self.plan, finalize=finalize,
            fuse=self.fuse, plan_cache=self.plan_cache,
            reports=self._report_log, label=label)

    # -- deprecated action shims (one collect() signature replaces them) -----

    def collect_async(self, label: Optional[str] = None) -> ActionHandle:
        """Deprecated: use ``collect(asynchronous=True)``."""
        warn_once(("method", "collect_async"),
                  "MaRe.collect_async(label=...) is deprecated; use "
                  "MaRe.collect(asynchronous=True, label=...)")
        return self.collect(asynchronous=True, label=label)

    def collect_first_shard(self) -> Any:
        """Deprecated: use ``collect(shard=0)``."""
        warn_once(("method", "collect_first_shard"),
                  "MaRe.collect_first_shard() is deprecated; use "
                  "MaRe.collect(shard=0)")
        return self.collect(shard=0)

    def collect_first_shard_async(self, label: Optional[str] = None
                                  ) -> ActionHandle:
        """Deprecated: use ``collect(shard=0, asynchronous=True)``."""
        warn_once(("method", "collect_first_shard_async"),
                  "MaRe.collect_first_shard_async(label=...) is "
                  "deprecated; use MaRe.collect(shard=0, "
                  "asynchronous=True, label=...)")
        return self.collect(shard=0, asynchronous=True, label=label)

    def num_partitions(self) -> int:
        return self._dataset.num_shards

    # -- observability -------------------------------------------------------

    def trace_to(self, path: str) -> str:
        """Export everything the process-wide tracer has recorded as
        Chrome-trace JSON (load at https://ui.perfetto.dev) and return
        ``path``.  Recording must be on — wrap the session (or the
        interesting actions) in ``repro.obs.tracing()`` or call
        ``repro.obs.TRACER.start()`` first; the instrumentation itself
        is always present and costs one branch per site while off."""
        from repro.obs import TRACER
        return TRACER.export(path)

    def metrics(self) -> dict:
        """Snapshot of the process-wide metrics registry: cache hits and
        evictions per tier, compile-cache hits/misses, exchanged-record
        volume, dispatch-queue depth, per-phase wall histograms."""
        from repro.obs import METRICS
        return METRICS.snapshot()

    def describe(self) -> str:
        """Human-readable view of the pending stage DAG (no execution),
        annotated with the inferred record schema at every stage boundary
        (``{schema}#capacity``; ``?`` where an op without a manifest makes
        it unknown).  Stages whose lineage node is materialized in the
        runtime cache — i.e. the prefix an action would NOT re-execute —
        are marked ``[cached]``.  A ``counters=[...]`` section lists
        every diagnostic counter the fused program will emit (stage
        index + kind), i.e. what an action's report will contain before
        anything runs."""
        states = self._stage_states()
        cached, _ = self.executor.cached_prefix(self._dataset, self.plan)
        if self.plan.empty:
            chain = "<identity>"
        else:
            chain = " -> ".join(
                f"{st.describe()} : {state.describe()}"
                + (" [cached]" if i < cached else "")
                for i, (st, state) in enumerate(zip(self.plan.stages,
                                                    states[1:])))
        specs = self.plan.counter_specs()
        counters = (", counters=[" + ", ".join(
            f"stage{i}.{kind}" for i, kind in specs) + "]") if specs else ""
        return (f"MaRe(shards={self._dataset.num_shards}, "
                f"cap={self._dataset.capacity}, "
                f"schema={states[0].describe()}, "
                f"plan=[{chain}]{counters})")
