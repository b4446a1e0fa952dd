"""repro.stream: continuous sources, incremental keyed aggregation,
windows, live queries — and the exactness contract: the incrementally
maintained aggregate is bit-identical to a one-shot reduce_by_key over
the union of all epochs, for ANY partition of the input into epochs."""
import os
import tempfile
import threading

import numpy as np
import pytest

import jax
from repro import compat
from repro.core import MaRe, PlanCache
from repro.io import text_source
from repro.runtime import Executor, MaterializationCache
from repro.serve import QueryService, ServiceConfig
from repro.stream import (ContinuousSource, IncrementalQuery, LiveQuery,
                          WindowedQuery)

NUM_KEYS = 7


def _mesh():
    return compat.make_mesh((jax.device_count(),), ("data",))


def _drop(root, name, lines):
    # stage outside the watched directory (same filesystem), then rename
    # in: a poller must never list the half-written staging file
    fd, staged = tempfile.mkstemp(dir=os.path.dirname(root))
    with os.fdopen(fd, "w") as f:
        f.write("\n".join(lines) + "\n")
    os.rename(staged, os.path.join(root, name))   # atomic arrival


def _lines(rng, n):
    return ["".join(rng.choice(list("ACGT"),
                               size=int(rng.integers(4, 30))))
            for _ in range(n)]


# module-level keyBy/valueBy: plan + lineage signatures key on callable
# identity, so the suffix must reuse the SAME objects every epoch
def _key7(recs):
    return (recs["data"][:, 0].astype(np.int32) % NUM_KEYS)


def _len_val(recs):
    return (recs["len"].astype(np.int32),)


def _oob_key(recs):
    return recs["len"].astype(np.int32) + 100    # far outside NUM_KEYS


def _build_for(op):
    def build(m):
        return m.reduce_by_key(_key7, value_by=_len_val, op=op,
                               num_keys=NUM_KEYS)
    return build


def _sorted_table(keys, vals, counts):
    order = np.argsort(keys)
    return keys[order], vals[order], counts[order]


def _query(root, build, **kw):
    kw.setdefault("plan_cache", PlanCache())
    kw.setdefault("executor", Executor(mat_cache=MaterializationCache()))
    cont = ContinuousSource(text_source(root), _mesh(), capacity=256)
    return IncrementalQuery(cont, build, **kw)


# -- exactness: any epoch partition == one-shot over the union ----------------

@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_incremental_equals_oneshot_for_any_epoch_partition(
        tmp_path, op, seed):
    rng = np.random.default_rng(seed)
    build = _build_for(op)
    q = _query(str(tmp_path), build)
    total = 0
    for epoch in range(int(rng.integers(2, 6))):
        _drop(str(tmp_path), f"part{epoch:03d}.txt",
              _lines(rng, int(rng.integers(2, 14))))
        update = q.update()
        assert update is not None and update.epoch == epoch
        total += update.new_splits
    keys, (vals,), counts = q.collect()
    one = build(MaRe.from_source(text_source(str(tmp_path)), _mesh(),
                                 capacity=1024))
    okeys, (ovals,), ocounts = one.collect()
    got = _sorted_table(np.asarray(keys), np.asarray(vals),
                        np.asarray(counts))
    want = _sorted_table(np.asarray(okeys), np.asarray(ovals),
                         np.asarray(ocounts))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype           # same dtype,
        assert np.array_equal(g, w)         # same values, exactly
    assert q.epoch == q.source.watermark


def test_incremental_zero_recompiles_after_first_epoch(tmp_path):
    rng = np.random.default_rng(7)
    pc = PlanCache()
    q = _query(str(tmp_path), _build_for("sum"), plan_cache=pc)
    epochs = 5
    for epoch in range(epochs):
        _drop(str(tmp_path), f"e{epoch}.txt", _lines(rng, 6))
        q.update()
    stats = pc.stats()
    # ONE delta program compiled at epoch 0, hit every epoch after;
    # ONE fold program compiled at epoch 1 (first two-table fold)
    assert stats["programs"] == 1
    assert stats["hits"] == epochs - 1
    assert q.fold_engine.compiles == 1
    assert q.fold_engine.folds == epochs - 1


def test_key_overflow_raises_like_oneshot(tmp_path):
    rng = np.random.default_rng(3)
    _drop(str(tmp_path), "bad.txt", _lines(rng, 5))

    def build(m):
        return m.reduce_by_key(_oob_key, value_by=_len_val, op="sum",
                               num_keys=NUM_KEYS)
    q = _query(str(tmp_path), build)
    with pytest.raises(RuntimeError, match="overflow"):
        q.update()
    one = build(MaRe.from_source(text_source(str(tmp_path)), _mesh(),
                                 capacity=256))
    with pytest.raises(RuntimeError, match="overflow"):
        one.collect()


# -- continuous source --------------------------------------------------------

def test_poll_is_monotone_and_consumes_no_empty_epochs(tmp_path):
    rng = np.random.default_rng(0)
    cont = ContinuousSource(text_source(str(tmp_path)), _mesh(),
                            capacity=64)
    assert cont.poll() is None and cont.watermark == -1
    _drop(str(tmp_path), "a.txt", _lines(rng, 3))
    batch = cont.poll()
    assert batch.epoch == 0 and batch.num_splits == 1
    assert cont.poll() is None           # same files -> nothing new
    _drop(str(tmp_path), "b.txt", _lines(rng, 3))
    _drop(str(tmp_path), "c.txt", _lines(rng, 3))
    batch = cont.poll()
    assert batch.epoch == 1 and batch.num_splits == 2   # one epoch, both
    assert len(cont.seen_splits()) == 3


def test_incremental_report_carries_stream_counters(tmp_path):
    rng = np.random.default_rng(1)
    q = _query(str(tmp_path), _build_for("sum"))
    _drop(str(tmp_path), "a.txt", _lines(rng, 4))
    q.update()
    _drop(str(tmp_path), "b.txt", _lines(rng, 4))
    update = q.update()
    rep = update.report
    assert rep is not None
    assert rep.counters["stream.epoch"] == 1
    assert rep.counters["stream.watermark"] == 1
    assert rep.counters["stream.new_splits"] == 1
    assert "stream.fold" in rep.phases
    assert "[incremental @ epoch 1]" in q.describe()


def test_generations_are_distinct_and_old_ones_dropped(tmp_path):
    rng = np.random.default_rng(2)
    executor = Executor(mat_cache=MaterializationCache())
    q = _query(str(tmp_path), _build_for("sum"), executor=executor)
    seen = set()
    epochs = 4
    for epoch in range(epochs):
        _drop(str(tmp_path), f"e{epoch}.txt", _lines(rng, 3))
        q.update()
        lineage = q.state.lineage
        assert lineage not in seen       # (base, watermark) per generation
        seen.add(lineage)
    stats = executor.mat_cache.stats()
    # every superseded generation was explicitly invalidated
    assert stats["invalidations"] == epochs - 1
    assert executor.mat_cache.get(q.state.lineage) is not None


# -- plan-suffix validation ---------------------------------------------------

def test_plan_must_end_in_reduce_by_key(tmp_path):
    rng = np.random.default_rng(4)
    _drop(str(tmp_path), "a.txt", _lines(rng, 3))
    q = _query(str(tmp_path), lambda m: m)       # identity plan
    with pytest.raises(ValueError, match="reduce_by_key"):
        q.update()


def test_build_must_produce_the_same_plan_every_epoch(tmp_path):
    rng = np.random.default_rng(5)
    builds = [_build_for("sum"), _build_for("max")]

    def unstable(m):
        return builds.pop(0)(m)
    q = _query(str(tmp_path), unstable)
    _drop(str(tmp_path), "a.txt", _lines(rng, 3))
    q.update()
    _drop(str(tmp_path), "b.txt", _lines(rng, 3))
    with pytest.raises(ValueError, match="SAME suffix"):
        q.update()


# -- windows ------------------------------------------------------------------

def _window_oneshot(tmp_path, build, names):
    root = str(tmp_path / "window-ref")
    os.makedirs(root, exist_ok=True)
    for name in names:
        data = open(os.path.join(str(tmp_path), name)).read()
        with open(os.path.join(root, name), "w") as f:
            f.write(data)
    one = build(MaRe.from_source(text_source(root), _mesh(),
                                 capacity=1024))
    return one.collect()


@pytest.mark.parametrize("size,slide", [(2, 1), (2, 2), (3, 3)])
def test_window_aggregate_covers_exactly_the_ring(tmp_path, size, slide):
    rng = np.random.default_rng(6)
    build = _build_for("sum")
    cont = ContinuousSource(text_source(str(tmp_path)), _mesh(),
                            capacity=256)
    w = WindowedQuery(cont, build, size=size, slide=slide,
                      plan_cache=PlanCache(),
                      executor=Executor(mat_cache=MaterializationCache()))
    epochs = 6
    names = []
    for epoch in range(epochs):
        name = f"e{epoch}.txt"
        names.append(name)
        _drop(str(tmp_path), name, _lines(rng, 5))
        w.update()
    # the last emission happened at the newest slide boundary; its window
    # is the `size` epochs ending there
    last_emit = (epochs // slide) * slide - 1
    covered = names[max(0, last_emit - size + 1):last_emit + 1]
    keys, (vals,), counts = w.collect()
    okeys, (ovals,), ocounts = _window_oneshot(tmp_path, build, covered)
    got = _sorted_table(np.asarray(keys), np.asarray(vals),
                        np.asarray(counts))
    want = _sorted_table(np.asarray(okeys), np.asarray(ovals),
                         np.asarray(ocounts))
    for g, x in zip(got, want):
        assert np.array_equal(g, x)
    assert w.window_epochs == tuple(
        range(max(0, epochs - size), epochs))
    assert w.evicted == epochs - size


def test_window_eviction_invalidates_cache_entries(tmp_path):
    rng = np.random.default_rng(8)
    executor = Executor(mat_cache=MaterializationCache())
    cont = ContinuousSource(text_source(str(tmp_path)), _mesh(),
                            capacity=128)
    w = WindowedQuery(cont, _build_for("sum"), size=2, slide=1,
                      plan_cache=PlanCache(), executor=executor)
    for epoch in range(4):
        _drop(str(tmp_path), f"e{epoch}.txt", _lines(rng, 3))
        w.update()
    # 2 expired per-epoch partials + superseded window generations
    assert executor.mat_cache.stats()["invalidations"] >= 2
    assert w.evicted == 2


def test_window_validates_size_and_slide(tmp_path):
    cont = ContinuousSource(text_source(str(tmp_path)), _mesh())
    with pytest.raises(ValueError, match="size"):
        WindowedQuery(cont, _build_for("sum"), size=0)
    with pytest.raises(ValueError, match="slide"):
        WindowedQuery(cont, _build_for("sum"), size=2, slide=3)
    t = WindowedQuery.tumbling(cont, _build_for("sum"), size=3)
    assert t.slide == t.size == 3


# -- sessions + live queries --------------------------------------------------

def _service():
    return QueryService(
        executor=Executor(plan_cache=PlanCache(),
                          mat_cache=MaterializationCache()),
        config=ServiceConfig(batch_window_s=0.0))


def test_session_stream_routes_reports_through_session(tmp_path):
    rng = np.random.default_rng(9)
    with _service() as svc:
        sess = svc.session("alice")
        cont = ContinuousSource(text_source(str(tmp_path)), _mesh(),
                                capacity=128)
        q = sess.stream(cont, _build_for("sum"))
        _drop(str(tmp_path), "a.txt", _lines(rng, 4))
        update = q.update()
        assert update is not None
        assert sess.reports.appended == 1
        rep = sess.report()
        assert rep.tenant == "alice"
        assert rep.counters["stream.epoch"] == 0
        assert rep.label.startswith("alice/stream")
        with pytest.raises(TypeError, match="reports"):
            sess.stream(cont, _build_for("sum"), reports=sess.reports)


def test_live_query_drives_follow_loop(tmp_path):
    rng = np.random.default_rng(10)
    with _service() as svc:
        sess = svc.session("alice")
        cont = ContinuousSource(text_source(str(tmp_path)), _mesh(),
                                capacity=128)
        q = sess.stream(cont, _build_for("sum"))
        refreshed = threading.Event()
        with LiveQuery(q, interval_s=0.05,
                       on_refresh=lambda _u: refreshed.set()) as live:
            _drop(str(tmp_path), "a.txt", _lines(rng, 4))
            reports = sess.follow(0, timeout=30.0)   # wakes per refresh
            assert reports and reports[0].tenant == "alice"
            assert refreshed.wait(timeout=30.0)
            assert live.running
        assert not live.running
        assert live.refreshes >= 1
        assert live.latest is not None and live.latest.epoch == 0


def test_live_query_surfaces_refresh_errors_on_stop(tmp_path):
    rng = np.random.default_rng(11)
    _drop(str(tmp_path), "bad.txt", _lines(rng, 3))

    def build(m):
        return m.reduce_by_key(_oob_key, value_by=_len_val, op="sum",
                               num_keys=NUM_KEYS)
    q = _query(str(tmp_path), build)
    live = LiveQuery(q, interval_s=0.05).start()
    deadline = 30.0
    while live.error is None and deadline > 0:
        threading.Event().wait(0.05)
        deadline -= 0.05
    with pytest.raises(RuntimeError, match="overflow"):
        live.stop()
