"""Device seconds per program scope and chip idle per program span
(``bench.scopes``): on hand-made events, on the committed v5e trace, on
the programs of a plan cache, and in a traced rehearsal (no chip plane,
so the readers stay silent).

No TPU is touched: ``ProfileData`` reads the file on the CPU."""
import dataclasses
from pathlib import Path

import numpy as np
import pytest

from bench import scopes
from bench.scopes import (OTHER, UNSCOPED, UNSPANNED, Profile, idle_totals,
                          read_profile, scope_totals, split_idle)
from bench.tests.harness import cell_args, result, run
from bench.trace import WINDOW, reduce_trace

FIXTURE = Path(__file__).parent / "data" / "gc_interactive_v5e.xplane.pb"
NEW_METRICS = {"map_s.kmer", "combine_s.kmer", "merge_s.kmer",
               "exchange_s.kmer", "unspanned_idle_s.kmer"}
NS = 1e9


def test_scope_totals_sum_per_scope_averaged_over_two_chips():
    mods = [(0, 10 * NS, "jit_mare_a(1)"), (11 * NS, 12 * NS, "jit_x(2)")]
    chip0 = ([(1 * NS, 3 * NS, "%fusion.2 = s32[8]{0} fusion(...)"),
              (3 * NS, 4 * NS, "%sort.7 = (s32[8]{0}) sort(...)"),
              (4 * NS, 5 * NS, "%copy.1 = s32[8]{0} copy(...)"),
              (9 * NS, 11 * NS, "%sort.7 = (s32[8]{0}) sort(...)"),
              (11 * NS, 12 * NS, "%fusion = f32[] fusion(...)")], mods)
    chip1 = ([(0, 4 * NS, "%fusion.2 = s32[8]{0} fusion(...)"),
              (5 * NS, 6 * NS, "%all-to-all.3 = s32[4]{0} all-to-all()")],
             mods)
    program = {"fusion.2": "s0.map", "sort.7": "s1.reduce_by_key/combine",
               "all-to-all.3": "s1.reduce_by_key/exchange",
               "copy.1": UNSCOPED}
    totals = scope_totals([chip0, chip1], (0, 10 * NS),
                          {"jit_mare_a": program})
    # sort.7 is clipped to the window; jit_x lies outside it
    assert totals == pytest.approx({
        "s0.map": (2 + 4) / 2, "s1.reduce_by_key/combine": (1 + 1) / 2,
        UNSCOPED: 1 / 2, "s1.reduce_by_key/exchange": 1 / 2})
    totals = scope_totals([chip0], (0, 12 * NS), {})
    assert totals == pytest.approx({OTHER: 7.0})
    # a loop and the ops of its body: each moment counts once, innermost
    loop = ([(0, 6 * NS, "%while.4 = (s32[]) while(...)"),
             (1 * NS, 2 * NS, "%fusion.2 = s32[8]{0} fusion(...)"),
             (2 * NS, 3 * NS, "%all-to-all.3 = s32[4]{0} all-to-all()")],
            mods)
    program["while.4"] = "s1.reduce_by_key/merge"
    assert scope_totals([loop], (0, 10 * NS),
                        {"jit_mare_a": program}) == pytest.approx({
        "s1.reduce_by_key/merge": 4.0, "s0.map": 1.0,
        "s1.reduce_by_key/exchange": 1.0})


def test_split_idle_by_overlap_across_two_spans_and_unspanned():
    spans = [(0, 10, "action"), (1, 4, "ingest"), (4, 8, "device_wait"),
             (8, 9, "collect.to_host"), (12, 14, "ingest")]
    # one gap [2, 6] runs from ingest into device_wait; [9, 13] from the
    # action's tail through no span into the next ingest
    got = split_idle([(2, 6), (9, 13)], spans)
    assert got == pytest.approx({"ingest": 2 + 1, "device_wait": 2,
                                 "action": 1, UNSPANNED: 2})
    assert sum(got.values()) == pytest.approx(4 + 4)
    assert split_idle([(0, 3)], []) == {UNSPANNED: 3}


def test_idle_totals_are_chip_zeros_gaps_and_need_program_spans():
    thread = [(0, 10 * NS, WINDOW), (0, 4 * NS, "ingest"),
              (4 * NS, 9 * NS, "device_wait"),
              (3 * NS, 5 * NS, "PjitFunction(jit(x))")]
    chip0 = ([(5 * NS, 9 * NS, "%f = s32[] fusion()")], [])
    chip1 = ([(0, 10 * NS, "%f = s32[] fusion()")], [])
    prof = Profile((0, 10 * NS), thread, [chip0, chip1])
    got = idle_totals(prof, {"ingest", "device_wait"})
    assert got == pytest.approx({"ingest": 4.0, "device_wait": 1.0,
                                 UNSPANNED: 1.0})
    # an older program puts no span in the profile: nothing to read
    assert idle_totals(prof, {"collect.to_host"}) is None


def test_committed_v5e_trace():
    prof = read_profile(str(FIXTURE))
    assert read_profile(str(FIXTURE)) is prof          # parsed once
    s = reduce_trace(str(FIXTURE))
    assert len(prof.chips) == 1
    assert (prof.window[1] - prof.window[0]) / NS == pytest.approx(
        s.window_s)
    # its program predates program names: every op is another program's
    totals = scope_totals(prof.chips, prof.window, {})
    assert list(totals) == [OTHER]
    assert totals[OTHER] >= s.busy_s - 1e-9
    # the harness's query annotations stand in for program spans here
    idle = idle_totals(prof, {"bench.query"})
    assert idle["bench.query"] > 0.9 * (s.window_s - s.busy_s)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                               abs=1e-6)


def test_program_scopes_of_the_plan_cache(monkeypatch):
    from repro.core import DEFAULT_CACHE, MaRe, PlanCache
    cache = PlanCache()
    m = MaRe((np.arange(64, dtype=np.int32) % 8,), plan_cache=cache)
    m.reduce_by_key(lambda r: r[0], op="sum", num_keys=8).collect()
    (prog,) = cache.programs()
    uncompiled = dataclasses.replace(prog, name="never_run", _aot=None,
                                     _scopes=None)

    class Older:                        # a program with no scopes
        pass

    monkeypatch.setattr(DEFAULT_CACHE, "programs",
                        lambda: [Older(), uncompiled, prog])
    got = scopes.program_scopes()
    assert list(got) == [f"jit_{prog.name}"]
    assert "s0.reduce_by_key/combine" in got[f"jit_{prog.name}"].values()
    monkeypatch.setattr(DEFAULT_CACHE, "programs", lambda: [Older()])
    assert scopes.program_scopes() is None


def test_traced_rehearsal_omits_the_scope_metrics():
    out = result(run(*cell_args("kmer12.batch.x4", trace=1)))
    assert out["correct"] is True
    assert {"ingest_s.kmer", "device_wait_s.kmer"} <= set(out["metrics"])
    assert not NEW_METRICS & set(out["metrics"])
