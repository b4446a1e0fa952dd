"""Observability: span tracer (ring, nesting, Chrome-trace export),
metrics registry, per-action phase breakdown, and the disabled-tracing
overhead bound."""
import json
import time

import numpy as np

from repro.core import MaRe, PlanCache
from repro.core.container import ContainerOp
from repro.io import text_source
from repro.obs import (TRACER, MetricsRegistry, Tracer, instant, span,
                       timed, tracing)
from repro.runtime import Executor, MaterializationCache


def _executor() -> Executor:
    return Executor(mat_cache=MaterializationCache())


def _ident_op(name="obs/id"):
    return ContainerOp(image=name, fn=lambda part, **kw: part)


# -- tracer unit behavior -----------------------------------------------------

def test_disabled_span_is_shared_null_object():
    assert not TRACER.enabled
    before = TRACER.events_total
    a, b = span("x", k=1), span("y")
    assert a is b                           # no allocation on the fast path
    with a as s:
        s.set(late=True)                    # all no-ops
    instant("nothing")
    assert TRACER.events_total == before


def test_nested_spans_are_contained_and_args_recorded():
    with tracing() as t:
        with span("outer", k=1) as sp:
            with span("inner"):
                pass
            sp.set(late=2)
        instant("marker", batch=3)
    assert not TRACER.enabled               # tracing() restored the state
    evs = t.events()
    assert [e["name"] for e in evs] == ["inner", "outer", "marker"]
    inner, outer, marker = evs
    assert outer["ts"] <= inner["ts"]
    assert (outer["ts"] + outer["dur"]) >= (inner["ts"] + inner["dur"])
    assert outer["args"] == {"k": 1, "late": 2}
    assert marker["ph"] == "i" and marker["args"] == {"batch": 3}
    assert all(e["ph"] == "X" for e in (inner, outer))


def test_ring_bounds_events_and_counts_drops():
    t = Tracer(capacity=8).start()
    for i in range(20):
        t.instant(f"e{i}")
    assert len(t.events()) == 8
    assert t.events_total == 20
    assert t.events_dropped == 12
    assert [e["name"] for e in t.events()] == [f"e{i}" for i in range(12, 20)]


def test_export_writes_valid_chrome_trace_object(tmp_path):
    with tracing() as t:
        with span("work", n=1):
            pass
    out = t.export(str(tmp_path / "trace.json"))
    with open(out) as f:
        payload = json.load(f)
    assert isinstance(payload["traceEvents"], list)
    assert payload["displayTimeUnit"] == "ms"
    assert payload["otherData"]["events_total"] == 1
    ev = payload["traceEvents"][0]
    assert ev["name"] == "work" and ev["ph"] == "X"
    assert {"ts", "dur", "pid", "tid"} <= set(ev)


def test_timed_accumulates_phases_with_tracing_off():
    assert not TRACER.enabled
    before = TRACER.events_total
    phases = {}
    with timed("p", phases):
        time.sleep(0.01)
    with timed("p", phases):
        pass
    assert phases["p"] >= 0.01              # accumulated across both blocks
    assert TRACER.events_total == before    # no span recorded while off


# -- metrics registry ---------------------------------------------------------

def test_metrics_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)                 # get-or-create: same instance
    reg.gauge("g").set(7)
    for v in (0.001, 0.002, 0.003):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 5
    assert snap["g"] == 7
    h = snap["h"]
    assert h["count"] == 3
    assert abs(h["mean"] - 0.002) < 1e-9
    assert h["min"] == 0.001 and h["max"] == 0.003
    text = reg.render()
    assert "c" in text and "count=3" in text
    assert reg.render(prefix="h").count("\n") == 0
    reg.reset()
    assert reg.snapshot() == {}


# -- integration: traced source-ingested action -------------------------------

def _contains(outer, inner):
    return (outer["tid"] == inner["tid"]
            and outer["ts"] <= inner["ts"]
            and outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"])


def test_traced_action_exports_nested_spans_and_phases(tmp_path):
    p = tmp_path / "d.txt"
    p.write_text("\n".join(f"line-{i:03d}" for i in range(64)) + "\n")
    ex = _executor()
    with tracing() as t:
        m = MaRe.from_source(text_source(str(p)), executor=ex)
        m.plan_cache = PlanCache()          # fresh: force a real compile
        q = m.repartition_by(
            lambda recs: (recs["data"][:, 0] % 3).astype("int32"))
        q.collect()
    out = t.export(str(tmp_path / "trace.json"))
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"ingest", "ingest.fetch", "ingest.frame", "ingest.gather",
            "ingest.device_put",
            "action", "plan.typecheck", "plan.build", "plan.lower",
            "plan.compile", "dispatch", "counter_sync"} <= names

    # nesting: each executor phase span sits inside an action span on the
    # same thread (Chrome-trace nesting is by containment)
    actions = [e for e in evs if e["name"] == "action"]
    for inner_name in ("plan.build", "plan.lower", "plan.compile",
                       "dispatch", "counter_sync"):
        inner = [e for e in evs if e["name"] == inner_name]
        assert inner, inner_name
        assert all(any(_contains(a, i) for a in actions) for i in inner), \
            inner_name
    # and each per-split fetch sits inside the top-level ingest span's
    # time window (fetches may run on pool threads, so time-only)
    ingest_ev = next(e for e in evs if e["name"] == "ingest")
    for f_ev in (e for e in evs if e["name"] == "ingest.fetch"):
        assert ingest_ev["ts"] <= f_ev["ts"]
        assert (ingest_ev["ts"] + ingest_ev["dur"]
                >= f_ev["ts"] + f_ev["dur"])

    # phase breakdown accounts for the action wall (acceptance: >= 90%)
    rep = q.report()
    assert rep.phases and {"plan.build", "plan.compile",
                           "dispatch"} <= set(rep.phases)
    total = sum(rep.phases.values())
    assert total >= 0.9 * rep.wall_s
    assert total <= rep.wall_s * 1.01       # phases are disjoint sub-spans


def test_mare_metrics_and_trace_to_surface(tmp_path):
    ex = _executor()
    m = MaRe((np.arange(32, dtype=np.int32),), plan_cache=PlanCache(),
             executor=ex).map(op=_ident_op())
    with tracing():
        m.collect()
    out = m.trace_to(str(tmp_path / "t.json"))
    with open(out) as f:
        assert any(e["name"] == "action"
                   for e in json.load(f)["traceEvents"])
    snap = m.metrics()
    assert snap["executor.actions"] >= 1
    assert "phase.dispatch" in snap


# -- overhead bound -----------------------------------------------------------

def test_disabled_tracing_overhead_under_5pct_of_small_action():
    """The instrumentation is always on; with no sink attached a span is
    one attribute load + branch.  Bound: crossing every site a warm fused
    action actually hits (action, cache_lookup, dispatch, device_wait,
    counter_sync + headroom: 16 spans) must cost < 5% of that action."""
    assert not TRACER.enabled
    ex = _executor()
    m = MaRe((np.arange(1 << 20, dtype=np.int32),), plan_cache=PlanCache(),
             executor=ex).map(op=_ident_op())
    m.collect()                             # compile once
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        m.collect()
    action_s = (time.perf_counter() - t0) / reps

    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("x"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span * 16 < 0.05 * action_s, (per_span, action_s)


# -- spans on the profiler's clock --------------------------------------------

def _host_span_names(log_dir) -> set:
    from jax.profiler import ProfileData
    (path,) = list(log_dir.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def _kmer_job(path, ex):
    from repro.io import fasta_source
    m = MaRe.from_source(fasta_source(str(path)), executor=ex)
    m.plan_cache = PlanCache()
    return (m.map(image="kmer-stats", k=4)
            .reduce_by_key(lambda r: r[0], value_by=lambda r: (r[1],),
                           op="sum").collect())


def test_spans_reach_the_profiler_host_plane_only_while_tracing(tmp_path):
    import jax
    rng = np.random.default_rng(3)
    fa = tmp_path / "reads.fa"
    fa.write_text("".join(
        f">r{i}\n{''.join(rng.choice(list('ACGT'), 40))}\n"
        for i in range(64)))
    program_spans = {"ingest", "dispatch", "device_wait", "collect.to_host"}
    on, off = tmp_path / "on", tmp_path / "off"
    with jax.profiler.trace(str(on)):
        with tracing() as t:
            _kmer_job(fa, _executor())
    assert program_spans <= {e["name"] for e in t.events()}
    assert program_spans <= _host_span_names(on)
    to_host = next(e for e in t.events() if e["name"] == "collect.to_host")
    assert to_host["args"]["records"] == 256       # every 4-mer occurs
    assert to_host["args"]["bytes"] == 256 * 3 * 4
    with jax.profiler.trace(str(off)):
        _kmer_job(fa, _executor())
    assert not program_spans & _host_span_names(off)


def test_disabled_span_constructs_no_annotation(monkeypatch):
    def refuse(name):
        raise AssertionError(f"annotation {name!r} built while off")

    monkeypatch.setattr(TRACER, "_annotation", refuse)
    assert not TRACER.enabled
    with span("x") as s, timed("y", {}):
        s.set(k=1)
    assert span("x") is span("y")           # the shared null span


# -- op scopes from a compiled program's text ---------------------------------

_HLO = '''HloModule jit_mare_x_0a1b2c, entry_computation_layout={()}

%fused_computation (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %add.1 = s32[8]{0} add(%p0, %p0), metadata={op_name="jit(f)/s0.map/add"}
  ROOT %copy.3 = s32[8]{0} copy(%add.1)
}

%fused_computation.1 (p1: s32[8]) -> s32[8] {
  %p1 = s32[8]{0} parameter(0)
  %neg.1 = s32[8]{0} negate(%p1), metadata={op_name="jit(f)/s1.reduce_by_key/merge/neg"}
  %neg.2 = s32[8]{0} negate(%neg.1), metadata={op_name="jit(f)/s1.reduce_by_key/merge/neg"}
  ROOT %mul.1 = s32[8]{0} multiply(%neg.2, %p1)
}

ENTRY %main (param.1: s32[8]) -> s32[8] {
  %param.1 = s32[8]{0} parameter(0), metadata={op_name="records"}
  %copy.1 = s32[8]{0} copy(%param.1)
  %fusion = s32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/s0.map/add"}
  %sort.2 = s32[8]{0} sort(%fusion), dimensions={0}, metadata={op_name="jit(f)/s1.reduce_by_key/combine/jit(sort)/sort"}
  %all-to-all.1 = s32[8]{0} all-to-all(%sort.2), metadata={op_name="jit(f)/s1.reduce_by_key/exchange/all_to_all"}
  %fusion.1 = s32[8]{0} fusion(%all-to-all.1), kind=kLoop, calls=%fused_computation.1
  %copy.2 = s32[8]{0} copy(%fusion.1)
  ROOT %tuple = (s32[8]{0}) tuple(%copy.2), metadata={op_name="jit(f)/s10.reduce/x"}
}
'''


def test_op_scopes_reads_own_callee_operand_and_user_scopes():
    import re
    from repro.obs import UNSCOPED, op_scopes
    scope = re.compile(
        r"(?:^|/)(s\d+\.[a-z_]+(?:/(?:combine|exchange|merge))?)(?=/|$)")
    got = op_scopes(_HLO, scope)
    assert got["fusion"] == got["add.1"] == "s0.map"       # own metadata
    assert got["sort.2"] == "s1.reduce_by_key/combine"
    assert got["all-to-all.1"] == "s1.reduce_by_key/exchange"
    assert got["tuple"] == "s10.reduce"
    # a fusion with no metadata: its root has none either, so the scope
    # most of its computation's instructions hold
    assert got["fusion.1"] == "s1.reduce_by_key/merge"
    assert got["copy.2"] == "s1.reduce_by_key/merge"       # its operand's
    assert got["copy.1"] == "s0.map"                       # its user's
    assert got["copy.3"] == "s0.map"                       # operand add.1
    assert got["p0"] == "s0.map"                           # user add.1
    assert got["param.1"] == UNSCOPED        # its only user has no scope
