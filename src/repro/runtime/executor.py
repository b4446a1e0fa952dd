"""The action engine: dispatch, counter sync, prefix reuse, async actions.

:mod:`repro.core.planner` stops at *lowering* — turning a stage plan into
a memoized compiled program.  Everything that happens when an action
actually fires lives here:

* **Prefix reuse** — before dispatching, the executor looks up the
  longest plan prefix whose lineage node is materialized in the
  :class:`~repro.runtime.cache.MaterializationCache`; the action starts
  from that cached dataset and only executes the suffix.  This is the
  interactive-processing half of the paper's claim (many queries over
  one persisted dataset pay the shared prefix once).
* **Counter sync** — stage counters (shuffle drops, key-table overflow,
  exchange volume) come back as outputs of the dispatched program and
  are checked ONCE per action, here, not per stage.
* **Structured diagnostics** — every action appends an
  :class:`~repro.runtime.reports.ActionReport` to a bounded history
  (``Executor.reports``) instead of overwriting a single dict.
* **Async actions** — :meth:`Executor.submit_action` queues the action
  on a single dispatch thread behind a *bounded* queue, returning an
  :class:`ActionHandle`; callers (e.g. the wave runner) overlap
  ingestion and host-side packing with compile + device execution while
  backpressure keeps at most ``max_pending`` actions in flight.

The eager path (``MaRe.collect``), the interactive prefix-cached path and
the out-of-core wave loop (:mod:`repro.io.waves`) all funnel through
:meth:`Executor.run` — one engine, one diagnostics channel.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core import planner as planner_lib
from repro.core.dataset import ShardedDataset
from repro.core.plan import Plan
from repro.obs import METRICS, span, timed
from repro.runtime.cache import MaterializationCache
from repro.runtime.lineage import Lineage, host_root
from repro.runtime.reports import ActionReport, ReportLog

#: Guards the check-then-set of ShardedDataset.lineage: an async action on
#: the dispatch thread and a describe()/action on the caller thread may
#: race to root the SAME dataset object — two distinct roots would orphan
#: whatever gets persisted under the losing one.
_LINEAGE_LOCK = threading.Lock()

#: Counter kinds reduced with max across shards instead of sum (bounds,
#: not totals) — see :func:`repro.core.plan.stage_counter_kinds`.
MAX_COUNTER_KINDS = frozenset({"max_send_count"})


def check_counters(counter_vec: jax.Array, specs, num_shards: int,
                   diagnostics: Optional[Dict[str, int]] = None,
                   stage_offset: int = 0) -> None:
    """One host sync for ALL stage counters, after the single dispatch.

    Error kinds (shuffle drops, keyed overflow) raise; informational
    kinds land in ``diagnostics`` (as do the error kinds, keyed
    ``"stage<i>.<kind>"``).  ``stage_offset`` shifts reported stage
    indices when the dispatched program was a suffix of a longer plan
    (prefix served from the materialization cache).

    Most kinds are totals and sum across shards; ``max_send_count`` is a
    bound and max-reduces instead — its diagnostic is the tightest
    per-destination ``capacity=`` that would have been lossless for any
    shard this run (the capacity-feedback knob for re-planning a skewed
    exchange).
    """
    grid = np.asarray(jax.device_get(counter_vec)).reshape(
        num_shards, len(specs))
    per = [int(grid[:, i].max()) if kind in MAX_COUNTER_KINDS
           else int(grid[:, i].sum())
           for i, (_, kind) in enumerate(specs)]
    for (stage_idx, kind), total in zip(specs, per):
        METRICS.counter(f"counters.{kind}").inc(int(total))
    if diagnostics is not None:
        for (stage_idx, kind), total in zip(specs, per):
            diagnostics[f"stage{stage_idx + stage_offset}.{kind}"] = \
                int(total)
    drops = [(stage_idx + stage_offset, int(total))
             for (stage_idx, kind), total in zip(specs, per)
             if kind == "shuffle_dropped" and total]
    if drops:
        total = sum(t for _, t in drops)
        raise RuntimeError(
            f"repartition_by overflow: {total} records dropped "
            f"(per stage: {drops}); raise `capacity` (paper analogue: "
            "partition exceeded tmpfs capacity — fall back to a larger "
            "staging area)")
    key_ovf = [(stage_idx + stage_offset, int(total))
               for (stage_idx, kind), total in zip(specs, per)
               if kind == "key_overflow" and total]
    if key_ovf:
        total = sum(t for _, t in key_ovf)
        raise RuntimeError(
            f"reduce_by_key key-table overflow: {total} records had keys "
            f"outside [0, num_keys) (per stage: {key_ovf}); raise "
            "`num_keys` or fix `key_by`")


def execute(ds: ShardedDataset, plan: Plan, *,
            cache: Optional["planner_lib.PlanCache"] = None,
            fuse: bool = True,
            diagnostics: Optional[Dict[str, int]] = None,
            stage_offset: int = 0,
            phases: Optional[Dict[str, float]] = None) -> ShardedDataset:
    """Dispatch a plan against a dataset (no lineage/report bookkeeping —
    that is :meth:`Executor.run`; this is the bare engine under it).

    ``fuse=True`` (default): one compiled program for the entire DAG,
    counters checked once after the single dispatch.  ``fuse=False``:
    stage-at-a-time execution (each stage its own program, counters
    synced after each stage) — the pre-planner schedule, kept for
    debugging and benchmarking.  ``diagnostics``, when given, is filled
    with per-counter totals keyed ``"stage<i>.<kind>"``, and for each
    keyed stage ``"stage<i>.local_keyed"``: 1 where the program lowered
    it for one device, without the exchange, and
    ``"stage<i>.sorted_keyed"``: 1 where it folds a two-word key by
    sorting (host-side facts of the compiled program, not device
    counters; a sorted stage's device counter ``distinct_keys`` sums
    like ``exchanged_records``); ``phases``,
    when given, accumulates the per-phase wall breakdown (lower /
    compile / dispatch / device_wait / counter_sync) that
    :class:`~repro.runtime.reports.ActionReport.phases` surfaces.
    """
    if plan.empty:
        return ds
    if not fuse:
        for i, stage in enumerate(plan.stages):
            ds = execute(ds, Plan(stages=(stage,)), cache=cache, fuse=True,
                         diagnostics=diagnostics,
                         stage_offset=stage_offset + i, phases=phases)
        return ds
    prog = planner_lib.compile_plan(plan, ds, cache, phases=phases)
    if diagnostics is not None:
        for i, local in prog.local_keyed.items():
            diagnostics[f"stage{i + stage_offset}.local_keyed"] = local
        for i, by_sort in prog.sorted_keyed.items():
            diagnostics[f"stage{i + stage_offset}.sorted_keyed"] = by_sort
    # AOT split: lowering + XLA compile become their own phases/spans
    # (zero on a plan-cache hit) instead of hiding in the first dispatch
    prog.ensure_compiled(ds.records, ds.counts, phases)
    with timed("dispatch", phases, stages=len(plan.stages)):
        outs = prog(ds.records, ds.counts)
    if prog.num_counters:
        out_records, out_counts, counter_vec = outs
    else:
        out_records, out_counts = outs
    # the dispatch above returns asynchronously-executing arrays; waiting
    # here attributes device time to the action that spent it rather
    # than to whoever touches the values first (collect, counter sync)
    with timed("device_wait", phases):
        jax.block_until_ready((out_records, out_counts))
    if prog.num_counters:
        with timed("counter_sync", phases,
                   num_counters=prog.num_counters):
            check_counters(counter_vec, prog.counters, ds.num_shards,
                           diagnostics, stage_offset)
    return ShardedDataset(records=out_records, counts=out_counts,
                          mesh=ds.mesh, axis=ds.axis)


class ActionHandle:
    """Future-like handle to an asynchronously dispatched action."""

    def __init__(self, label: Optional[str] = None) -> None:
        self.label = label
        self.report: Optional[ActionReport] = None
        #: Set by Executor.submit / the dispatch worker: when the action
        #: entered the queue and when the worker dequeued it.
        self.submitted_at: Optional[float] = None
        self.started_at: Optional[float] = None
        self._done = threading.Event()
        self._value: Any = None
        self._error: Optional[BaseException] = None

    @property
    def queue_wait_s(self) -> float:
        """Seconds spent queued behind earlier actions (0.0 until the
        dispatch worker picks this action up)."""
        if self.submitted_at is None or self.started_at is None:
            return 0.0
        return max(0.0, self.started_at - self.submitted_at)

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> Any:
        """Block for the action's value.  A ``TimeoutError`` does NOT
        poison the handle: a later ``result()`` call still succeeds once
        the action completes."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"action {self.label or ''} still pending")
        if self._error is not None:
            raise self._error
        return self._value

    # -- producer side (executor thread only) --------------------------------

    def _finish(self, value: Any = None,
                error: Optional[BaseException] = None) -> None:
        self._value = value
        self._error = error
        self._done.set()


class Executor:
    """Owns action dispatch against one pair of caches.

    ``plan_cache`` — compiled-program memoization (defaults to the
    process-wide :data:`repro.core.planner.DEFAULT_CACHE`; a per-action
    override may be passed to :meth:`run`, which MaRe uses to honor its
    ``plan_cache=`` knob).  ``mat_cache`` — the lineage-keyed
    materialization store that ``persist()`` feeds and prefix lookup
    reads.  ``max_pending`` bounds the async dispatch queue (submitting
    beyond it blocks the caller — backpressure, not unbounded buffering).
    """

    def __init__(self, plan_cache: Optional["planner_lib.PlanCache"] = None,
                 mat_cache: Optional[MaterializationCache] = None,
                 max_pending: int = 2,
                 report_history: int = 256) -> None:
        self.plan_cache = plan_cache
        self.mat_cache = mat_cache if mat_cache is not None \
            else MaterializationCache()
        self.reports = ReportLog(report_history)
        self.max_pending = max_pending
        self._run_lock = threading.RLock()
        self._queue: "queue.Queue" = queue.Queue(maxsize=max_pending)
        self._worker: Optional[threading.Thread] = None
        self._worker_lock = threading.Lock()

    # -- lineage -------------------------------------------------------------

    def ensure_lineage(self, ds: ShardedDataset) -> Lineage:
        """Dataset's lineage root, assigning a fresh host root once for
        datasets of unknown provenance (mutates ``ds`` in place so every
        handle over the same dataset object shares the root)."""
        if ds.lineage is None:
            with _LINEAGE_LOCK:
                if ds.lineage is None:
                    ds.lineage = host_root()
        return ds.lineage

    def cached_prefix(self, ds: ShardedDataset, plan: Plan
                      ) -> Tuple[int, Optional[Lineage]]:
        """(stage count, lineage) of the longest materialized plan prefix
        — key lookup only, safe for ``describe()``."""
        if plan.empty:
            return 0, None
        return self.mat_cache.longest_prefix(self.ensure_lineage(ds), plan)

    # -- synchronous actions -------------------------------------------------

    def run(self, ds: ShardedDataset, plan: Plan, *,
            fuse: bool = True,
            plan_cache: Optional["planner_lib.PlanCache"] = None,
            reports: Optional[ReportLog] = None,
            label: Optional[str] = None,
            queue_wait_s: float = 0.0,
            tenant: Optional[str] = None
            ) -> Tuple[ShardedDataset, ActionReport]:
        """Run one action: prefix lookup, suffix dispatch, counter check,
        report.  Returns the materialized dataset (lineage = root +
        whole plan) and the action's report.  ``queue_wait_s`` is the
        async path's measured time-on-queue, recorded on the report
        (execution wall time starts here, not at submit); ``tenant``
        tags the report and the cache lookup with the serving-layer
        session that issued the action."""
        cache = plan_cache if plan_cache is not None else self.plan_cache
        cache = cache if cache is not None else planner_lib.DEFAULT_CACHE
        with self._run_lock, span("action", plan=plan.describe(),
                                  label=label) as action_span:
            t0 = time.monotonic()
            before = cache.stats()
            root = self.ensure_lineage(ds)
            result_lineage = root.extend(plan)
            counters: Dict[str, int] = {}
            phases: Dict[str, float] = {}
            cached_stages, cache_tier = 0, None
            if not plan.empty:
                with timed("cache_lookup", phases):
                    k, tier, cached = self.mat_cache.lookup_prefix(
                        root, plan, tenant=tenant)
                if cached is not None:
                    ds = cached
                    cached_stages = k
                    cache_tier = tier
                ds = execute(ds, plan.drop(cached_stages), cache=cache,
                             fuse=fuse, diagnostics=counters,
                             stage_offset=cached_stages, phases=phases)
                ds.lineage = result_lineage
            after = cache.stats()
            report = ActionReport(
                action_id=self.reports.new_id(),
                plan=plan.describe(),
                total_stages=len(plan.stages),
                cached_stages=cached_stages,
                cache_tier=cache_tier,
                lineage=ds.lineage.digest() if ds.lineage else None,
                counters=counters,
                programs_compiled=after["misses"] - before["misses"],
                program_cache_hits=after["hits"] - before["hits"],
                wall_s=time.monotonic() - t0,
                phases=phases,
                queue_wait_s=queue_wait_s,
                label=label,
                tenant=tenant)
            action_span.set(action_id=report.action_id,
                            cached_stages=cached_stages)
            METRICS.counter("executor.actions").inc()
            for phase, s in phases.items():
                METRICS.histogram(f"phase.{phase}").observe(s)
            if queue_wait_s:
                METRICS.histogram("phase.queue_wait").observe(queue_wait_s)
            self.reports.append(report)
            if reports is not None:
                reports.append(report)
            return ds, report

    def persist(self, ds: ShardedDataset, tier: str = "device",
                owner: Optional[str] = None):
        """Register a materialized dataset in the materialization cache
        under its lineage (``MaRe.persist()``'s engine half).  ``owner``
        charges the entry to that tenant's cache-budget partition."""
        self.ensure_lineage(ds)
        return self.mat_cache.put(ds, tier=tier, owner=owner)

    # -- async actions -------------------------------------------------------

    def _ensure_worker(self) -> None:
        with self._worker_lock:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._drain, name="repro-runtime-executor",
                    daemon=True)
                self._worker.start()

    def _drain(self) -> None:
        while True:
            handle, fn = self._queue.get()
            METRICS.gauge("executor.queue_depth").set(self._queue.qsize())
            handle.started_at = time.monotonic()
            try:
                handle._finish(value=fn(handle))
            except BaseException as e:          # delivered via result()
                handle._finish(error=e)
            finally:
                self._queue.task_done()

    def submit(self, fn: Callable[[ActionHandle], Any],
               label: Optional[str] = None) -> ActionHandle:
        """Queue ``fn(handle)`` on the dispatch thread (FIFO, bounded:
        blocks when ``max_pending`` actions are already queued)."""
        self._ensure_worker()
        handle = ActionHandle(label=label)
        handle.submitted_at = time.monotonic()
        self._queue.put((handle, fn))
        METRICS.gauge("executor.queue_depth").set(self._queue.qsize())
        METRICS.counter("executor.submitted").inc()
        return handle

    def submit_action(self, ds: ShardedDataset, plan: Plan, *,
                      finalize: Optional[Callable[[ShardedDataset], Any]]
                      = None,
                      fuse: bool = True,
                      plan_cache: Optional["planner_lib.PlanCache"] = None,
                      reports: Optional[ReportLog] = None,
                      label: Optional[str] = None,
                      tenant: Optional[str] = None) -> ActionHandle:
        """Async :meth:`run`: dispatch the plan on the executor thread and
        (optionally) post-process the materialized dataset with
        ``finalize`` (e.g. ``dataset.collect``); the handle resolves to
        ``finalize(ds)`` (or the dataset itself).  Queue wait (submit ->
        worker dequeue) is measured separately from execution and lands
        in ``report.queue_wait_s`` — a backed-up queue no longer makes
        an action's ``wall_s`` look idle-fast."""

        def action(handle: ActionHandle) -> Any:
            out, report = self.run(ds, plan, fuse=fuse,
                                   plan_cache=plan_cache, reports=reports,
                                   label=label,
                                   queue_wait_s=handle.queue_wait_s,
                                   tenant=tenant)
            handle.report = report
            return finalize(out) if finalize is not None else out

        return self.submit(action, label=label)


#: Process-wide default engine: MaRe actions and WaveRunner waves share it
#: (and, through it, the planner's DEFAULT_CACHE), so interactive handles,
#: eager actions and out-of-core waves see one materialization cache and
#: one report history.
DEFAULT_EXECUTOR = Executor()
