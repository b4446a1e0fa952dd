"""The trace reduction, on a trace recorded on one TPU v5e (four seconds
of the interactive cell: 81 Listing 1 queries) and on hand-made events.

No TPU is touched: ``ProfileData`` reads the file on the CPU."""
from pathlib import Path

import pytest

from bench.trace import (NO_SPAN, WINDOW, op_label, read_xplane,
                         reduce_trace, summarize)

FIXTURE = Path(__file__).parent / "data" / "gc_interactive_v5e.xplane.pb"


@pytest.fixture(scope="module")
def chip_trace():
    return reduce_trace(str(FIXTURE))


def test_recorded_trace_idle_share(chip_trace):
    s = chip_trace
    assert s.chips == 1
    assert s.window_s == pytest.approx(4.051914322, abs=1e-9)
    assert s.busy_s == pytest.approx(3.787621909, abs=1e-9)
    assert s.idle_pct == pytest.approx(
        100 * (1 - 3.787621909 / 4.051914322))


def test_recorded_trace_op_breakdown(chip_trace):
    ops = chip_trace.device_ops
    assert len(ops) == 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    # the grep-chars scan over the resident [8388608, 160] bytes leads
    assert ops[0][0] == "jit_interior compare_reduce_fusion pred[1342177280]"
    assert ops[0][1] == pytest.approx(1.397583143, abs=1e-9)
    assert sum(v for _, v in ops) <= chip_trace.busy_s + 1e-9


def test_recorded_trace_gaps_fall_in_queries(chip_trace):
    # every idle stretch of the window lies inside a query's annotation
    assert [n for n, _ in chip_trace.idle_gaps] == ["bench.query"]
    idle = sum(v for _, v in chip_trace.idle_gaps)
    assert idle == pytest.approx(chip_trace.window_s - chip_trace.busy_s,
                                 abs=1e-6)


def test_recorded_trace_has_no_framework_names():
    # ops are named by HLO text only; jit names such as
    # segment_reduce_impl do not reach the device events
    host, chips = read_xplane(str(FIXTURE))
    (ops, mods), = chips
    assert all("jit(" not in name for _, _, name in ops)
    assert {m[2].split("(")[0] for m in mods} == {"jit_interior"}
    assert sum(n == WINDOW for _, _, n in host) == 1


def test_summarize_two_chips_and_nested_spans():
    ns = 1e9
    host = [(0, 10 * ns, WINDOW), (1 * ns, 4 * ns, "bench.job"),
            (1 * ns, 2 * ns, "bench.ingest"), (6 * ns, 7 * ns, "bench.x")]
    mods = [(0, 10 * ns, "jit_prog(123)")]
    chip0 = ([(2 * ns, 5 * ns, "%fusion.1 = s32[8]{0} fusion(...)"),
              (4 * ns, 6 * ns, "%sort.2 = (s32[8]{0}) sort(...)"),
              (9 * ns, 12 * ns, "%fusion.1 = s32[8]{0} fusion(...)")],
             mods)
    chip1 = ([(0, 10 * ns, "%all-to-all.3 = s32[4]{0} all-to-all(...)")],
             mods)
    s = summarize(host, [chip0, chip1])
    # chip 0 busy [2, 6] and [9, 10]: 5 s; chip 1 the whole 10 s
    assert s.busy_s == pytest.approx(7.5)
    assert s.window_s == pytest.approx(10)
    assert dict(s.device_ops) == pytest.approx({
        "jit_prog fusion.1 s32[8]": 2.0, "jit_prog sort.2 (s32[8]": 1.0,
        "jit_prog all-to-all.3 s32[4]": 5.0})
    # chip 0 is idle over [0, 2], whose middle lies in bench.job and, the
    # innermost, bench.ingest; and over [6, 9], whose middle is in no span
    assert dict(s.idle_gaps) == pytest.approx({"bench.ingest": 2.0,
                                               NO_SPAN: 3.0})
    host.append((0.8 * ns, 1.2 * ns, "bench.inner"))
    host.append((7 * ns, 8 * ns, "bench.late"))
    s = summarize(host, [chip0])
    assert dict(s.idle_gaps) == pytest.approx({"bench.inner": 2.0,
                                               "bench.late": 3.0})


def test_summarize_without_window_or_chip_is_none():
    assert summarize([(0, 1, "bench.job")], [([], [])]) is None
    assert summarize([(0, 1, WINDOW)], []) is None


def test_op_label():
    text = "%copy.1 = u8[8,160]{0,1:T(8,128)} copy(u8[8,160]{1,0} %p)"
    assert op_label("jit_interior", text) == "jit_interior copy.1 u8[8,160]"
