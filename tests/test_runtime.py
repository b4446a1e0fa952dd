"""Runtime layer: lineage-keyed materialization cache (prefix reuse,
budgeted LRU tiers), async action engine, per-action report history."""
import threading
import time

import jax
import numpy as np
import pytest

from repro import compat
from repro.core import MaRe, PlanCache, from_host
from repro.core.container import ContainerOp
from repro.io import text_source
from repro.runtime import (Executor, MaterializationCache, estimate_nbytes,
                           host_root)
from repro.runtime.reports import ActionReport, ReportLog


def _executor(**cache_kw) -> Executor:
    return Executor(mat_cache=MaterializationCache(**cache_kw))


def _counting_op(name="rt/counter"):
    """An op whose fn counts how many times it is TRACED (not executed):
    a cached-prefix action compiles a suffix-only program, so the prefix
    op must not appear in any new trace."""
    traces = {"n": 0}

    def fn(part, **kw):
        traces["n"] += 1
        return part

    return ContainerOp(image=name, fn=fn), traces


def _ident_op(name="rt/id"):
    return ContainerOp(image=name, fn=lambda part, **kw: part)


def _key_mod3(recs):
    return recs[0] % 3


def _data(n=32, seed=0):
    return (np.arange(n, dtype=np.int32),)


# -- prefix cache: hit/miss across forked handles -----------------------------

def test_persist_prefix_hit_on_forked_handle():
    op, traces = _counting_op()
    cache = PlanCache()
    ex = _executor()
    base = MaRe(_data(), plan_cache=cache, executor=ex)

    base.map(op=op).persist()
    traces_after_persist = traces["n"]
    assert traces_after_persist == 1

    # a FORK of base rebuilding the same map prefix + a new suffix: the
    # prefix is served from the cache, so the suffix-only program never
    # traces the map op again
    q = base.map(op=op).repartition_by(_key_mod3)
    got = q.collect()
    assert sorted(got[0].tolist()) == list(range(32))
    assert traces["n"] == traces_after_persist
    report = q.report()
    assert report.cached_stages == 1 and report.total_stages == 2
    assert report.cache_tier == "device"


def test_whole_plan_hit_compiles_and_executes_nothing():
    op, traces = _counting_op()
    cache = PlanCache()
    ex = _executor()
    base = MaRe(_data(), plan_cache=cache, executor=ex)
    base.map(op=op).persist()
    compiles_after_persist = cache.stats()["misses"]

    q = base.map(op=op)                     # exactly the persisted plan
    got = q.collect()
    assert sorted(got[0].tolist()) == list(range(32))
    report = q.report()
    assert report.cached_stages == report.total_stages == 1
    assert report.programs_compiled == 0
    assert cache.stats()["misses"] == compiles_after_persist


def test_different_prefix_misses():
    op_a, _ = _counting_op("rt/a")
    op_b, traces_b = _counting_op("rt/b")
    ex = _executor()
    base = MaRe(_data(), plan_cache=PlanCache(), executor=ex)
    base.map(op=op_a).persist()

    q = base.map(op=op_b)                   # different op -> different node
    q.collect()
    assert q.report().cached_stages == 0
    assert traces_b["n"] == 1               # really executed


def test_separately_parallelized_hosts_do_not_share_lineage():
    """Equal host arrays parallelized twice get distinct roots — content
    identity is unknown, so never a false hit."""
    op, _ = _counting_op()
    ex = _executor()
    MaRe(_data(), plan_cache=PlanCache(), executor=ex).map(op=op).persist()
    q = MaRe(_data(), plan_cache=PlanCache(), executor=ex).map(op=op)
    q.collect()
    assert q.report().cached_stages == 0


def test_cache_is_persist_sugar():
    op, _ = _counting_op()
    ex = _executor()
    base = MaRe(_data(), plan_cache=PlanCache(), executor=ex)
    cached = base.map(op=op).cache()
    assert len(ex.mat_cache) == 1
    assert cached.plan.empty
    q = base.map(op=op)
    q.collect()
    assert q.report().cached_stages == 1


def test_ingest_lineage_is_content_keyed(tmp_path):
    """Re-opening the same source reaches materializations persisted by a
    previous handle (roots digest the resolved splits + geometry)."""
    p = tmp_path / "d.txt"
    p.write_text("\n".join(f"line-{i}" for i in range(50)) + "\n")
    op, traces = _counting_op()
    ex = _executor()
    cache = PlanCache()

    m1 = MaRe.from_source(text_source(str(p)), executor=ex)
    m1.plan_cache = cache
    m1.map(op=op).persist()
    after_persist = traces["n"]

    m2 = MaRe.from_source(text_source(str(p)), executor=ex)
    m2.plan_cache = cache
    q = m2.map(op=op)
    q.collect()
    assert q.report().cached_stages == 1
    assert traces["n"] == after_persist


# -- budgeted LRU tiers -------------------------------------------------------

def _tiny_ds(mesh, n=8, fill=0):
    ds = from_host((np.full(n, fill, np.int32),), mesh)
    ds.lineage = host_root("test")
    return ds


def test_estimate_nbytes_schema_based():
    mesh = compat.make_mesh((1,), ("data",))
    ds = _tiny_ds(mesh, n=8)
    assert estimate_nbytes(ds) == 8 * 4 + 4     # records + counts


def test_device_eviction_spills_to_host_then_hits():
    mesh = compat.make_mesh((1,), ("data",))
    a, b = _tiny_ds(mesh, fill=1), _tiny_ds(mesh, fill=2)
    # budget fits exactly one 36-byte entry: putting b evicts a (LRU)
    cache = MaterializationCache(device_budget_bytes=40)
    cache.put(a)
    cache.put(b)
    assert cache.stats()["spills"] == 1
    assert cache.entry(a.lineage).tier == "host"
    assert cache.entry(b.lineage).tier == "device"

    got = cache.get(a.lineage)              # host hit: re-placed on mesh
    assert got is not None
    assert np.asarray(got.records[0]).tolist() == [1] * 8
    assert got.lineage == a.lineage
    stats = cache.stats()
    assert stats["hits"] == 1 and stats["host_hits"] == 1


def test_host_eviction_drops_lru():
    mesh = compat.make_mesh((1,), ("data",))
    a, b = _tiny_ds(mesh, fill=1), _tiny_ds(mesh, fill=2)
    cache = MaterializationCache(device_budget_bytes=40,
                                 host_budget_bytes=40)
    cache.put(a)
    cache.put(b)                            # a spills to host (fits)
    c = _tiny_ds(mesh, fill=3)
    cache.put(c)                            # b spills; host over budget
    stats = cache.stats()
    assert stats["spills"] == 2
    assert stats["drops"] == 1
    assert cache.entry(a.lineage) is None   # LRU host entry dropped
    assert cache.entry(b.lineage).tier == "host"
    assert cache.entry(c.lineage).tier == "device"
    assert cache.get(a.lineage) is None     # recompute from lineage


def test_prefix_hit_from_host_tier_via_executor():
    op, traces = _counting_op()
    # device budget below one dataset: persist lands on device then is
    # immediately spilled -> the later hit comes from the host tier
    ex = _executor(device_budget_bytes=1)
    base = MaRe(_data(), plan_cache=PlanCache(), executor=ex)
    base.map(op=op).persist()
    assert ex.mat_cache.stats()["spills"] == 1

    q = base.map(op=op).repartition_by(_key_mod3)
    got = q.collect()
    assert sorted(got[0].tolist()) == list(range(32))
    assert q.report().cached_stages == 1
    assert q.report().cache_tier == "host"
    assert traces["n"] == 1                 # prefix still not re-traced


# -- async action engine ------------------------------------------------------

def test_async_actions_preserve_fifo_order():
    op, _ = _counting_op()
    ex = _executor()
    cache = PlanCache()
    handles = []
    for i in range(5):
        m = MaRe((np.full(16, i, np.int32),), plan_cache=cache,
                 executor=ex).map(op=op)
        handles.append(m.collect(asynchronous=True, label=f"q{i}"))
    for i, h in enumerate(handles):
        got = h.result(timeout=60)
        assert got[0].tolist() == [i] * 16
        assert h.done()
        assert h.report is not None and h.report.label == f"q{i}"
    assert [r.label for r in ex.reports] == [f"q{i}" for i in range(5)]
    ids = [r.action_id for r in ex.reports]
    assert ids == sorted(ids)               # dispatched in submit order


def test_async_action_delivers_exceptions():
    ex = _executor()
    m = (MaRe((np.arange(4 * jax.device_count(), dtype=np.int32),),
              plan_cache=PlanCache(), executor=ex)
         .repartition_by(lambda recs: recs[0] * 0, capacity=1))
    h = m.collect(asynchronous=True)
    with pytest.raises(RuntimeError, match="overflow"):
        h.result(timeout=60)


def test_async_result_timeout_does_not_poison_handle():
    ex = _executor()
    release = threading.Event()
    h = ex.submit(lambda handle: (release.wait(30), "ok")[1], label="slow")
    with pytest.raises(TimeoutError):
        h.result(timeout=0.05)
    assert not h.done()
    release.set()
    assert h.result(timeout=30) == "ok"     # later call still succeeds
    assert h.done()


def test_queue_wait_measured_separately_from_execution():
    ex = _executor()
    gate = threading.Event()
    ex.submit(lambda handle: gate.wait(30))     # hog the dispatch thread
    op, _ = _counting_op("rt/qw")
    m = MaRe(_data(), plan_cache=PlanCache(), executor=ex).map(op=op)
    t_submit = time.monotonic()
    h = m.collect(asynchronous=True, label="queued")
    time.sleep(0.25)
    gate.set()
    h.result(timeout=60)
    elapsed = time.monotonic() - t_submit
    assert h.queue_wait_s >= 0.2
    rep = h.report
    assert rep.queue_wait_s == h.queue_wait_s
    assert f"queue_wait={rep.queue_wait_s * 1e3:.1f}ms" in rep.describe()
    # wait and execution are disjoint sub-intervals of submit->result:
    # wall_s starts at dequeue, the wait is not folded into it
    assert rep.queue_wait_s + rep.wall_s <= elapsed + 0.05


def test_reportlog_overflow_bounds_history_but_counts_monotonically():
    log = ReportLog(maxlen=4)
    for _ in range(10):
        log.append(ActionReport(action_id=log.new_id(), plan="p",
                                total_stages=1))
    assert len(log) == 4                    # history bounded at maxlen
    assert log.appended == 10               # lifetime count keeps going
    assert [r.action_id for r in log] == [6, 7, 8, 9]
    assert log.new_id() == 10               # ids never reused
    assert log.latest.action_id == 9


def test_reportlog_summary_renders_phase_table():
    log = ReportLog()
    assert log.summary() == "ReportLog: no actions recorded"
    log.append(ActionReport(action_id=0, plan="p", total_stages=2,
                            cached_stages=1, programs_compiled=1,
                            wall_s=0.2, queue_wait_s=0.1,
                            phases={"dispatch": 0.15,
                                    "counter_sync": 0.05}))
    s = log.summary()
    assert "1 retained / 1 total actions" in s
    assert "queue_wait=0.100s" in s
    assert "2 planned, 1 served from cache" in s
    assert "programs compiled: 1" in s
    assert "dispatch" in s and "75.0%" in s     # 0.15 / 0.2 wall
    assert log.phase_totals() == {"dispatch": 0.15, "counter_sync": 0.05}


def test_async_is_snapshot_not_mutation():
    op, _ = _counting_op()
    ex = _executor()
    m = MaRe(_data(), plan_cache=PlanCache(), executor=ex).map(op=op)
    h = m.collect(asynchronous=True)
    h.result(timeout=60)
    assert not m.plan.empty                 # handle left lazy


# -- reports & diagnostics ----------------------------------------------------

def _key_first(recs):
    return recs[0]


def _val_second(recs):
    return (recs[1],)


def test_report_diagnostics_survive_chaining():
    keys = np.array([0, 1, 2, 3] * 8, np.int32)
    vals = np.ones(32, np.float32)
    ex = _executor()
    m = MaRe((keys, vals), plan_cache=PlanCache(),
             executor=ex).reduce_by_key(_key_first, value_by=_val_second,
                                        op="sum", num_keys=4)
    m.collect()
    diag = m.report().diagnostics
    assert diag["stage0.exchanged_records"] > 0

    chained = m.map(op=_ident_op())         # pre-runtime: history vanished
    assert chained.report().diagnostics == diag
    chained.collect()
    assert len(chained.reports()) == 2
    assert chained.reports()[0].counters == diag
    assert chained.report().diagnostics == {}  # map-only action: no counters


def test_report_counters_keep_absolute_stage_indices_after_prefix_hit():
    """A suffix executed after a cached prefix reports counters under the
    ORIGINAL stage indices, not suffix-relative ones."""
    op, _ = _counting_op()
    keys = np.array([0, 1, 2, 3] * 8, np.int32)
    vals = np.ones(32, np.float32)
    ex = _executor()
    base = MaRe((keys, vals), plan_cache=PlanCache(), executor=ex)
    base.map(op=op).persist()
    q = base.map(op=op).reduce_by_key(_key_first, value_by=_val_second,
                                      op="sum", num_keys=4)
    q.collect()
    report = q.report()
    assert report.cached_stages == 1
    assert "stage1.exchanged_records" in report.counters
    assert q.reports().total("exchanged_records") > 0


def test_describe_lists_keyed_reduce_counter_specs():
    m = MaRe((np.array([0, 1] * 16, np.int32), np.ones(32, np.float32)),
             plan_cache=PlanCache(), executor=_executor()
             ).reduce_by_key(_key_first, value_by=_val_second, op="sum",
                             num_keys=2)
    d = m.describe()
    assert "counters=[" in d
    assert "stage0.key_overflow" in d
    assert "stage0.exchanged_records" in d


# -- golden describe ----------------------------------------------------------

def test_describe_annotates_cached_lineage_nodes_golden():
    mesh = compat.make_mesh((1,), ("data",))
    ds = from_host((np.arange(8, dtype=np.int32),), mesh)
    ex = _executor()
    cache = PlanCache()
    op = _ident_op()
    base = MaRe(ds, plan_cache=cache, executor=ex)
    base.map(op=op).persist()

    q = base.map(op=op).repartition_by(_key_mod3)
    assert q.describe() == (
        "MaRe(shards=1, cap=8, schema=(i32)#8, "
        "plan=[map[rt/id:latest] : ?#? [cached] -> "
        "shuffle(cap=None) : ?#?], counters=[stage1.shuffle_dropped])")
    # the persisted node is marked; the suffix is not
    fresh = MaRe(from_host((np.arange(8, dtype=np.int32),), mesh),
                 plan_cache=cache, executor=ex).map(op=op)
    assert "[cached]" not in fresh.describe()


# -- persistent compile cache placement ---------------------------------------

def test_compile_cache_honours_env_and_sets_nothing(monkeypatch, tmp_path):
    from repro import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    import os
    from repro import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
