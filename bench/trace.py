"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics and the ``breakdown`` read.

On a TPU each chip is a plane ``/device:TPU:<i>`` whose line ``XLA Ops``
holds one event per HLO operation that ran, named by its HLO text
(``%fusion.11 = s32[16777216]{...} fusion(...), kind=kCustom, ...``),
and whose line ``XLA Modules`` holds one event per program run. The
host's own annotations (``jax.profiler.TraceAnnotation``, named
``bench.*`` by the harness) lie on the ``/host:CPU`` plane, on the same
clock.

* busy: the union of a chip's op intervals inside the window
  (``bench.window``), averaged over the chips;
* device ops: seconds per (program, HLO op) summed over the window,
  averaged over the chips;
* idle gaps: each stretch inside the window in which chip 0 ran no op is
  named by the innermost ``bench.*`` annotation that holds its middle
  (``no host span`` where none does); seconds are summed per name.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
NO_SPAN = "no host span"

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                     # averaged over the chips
    chips: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(module: str, hlo_text: str) -> str:
    """``<program> <op> <output type>`` from an XLA Ops event's name."""
    name, _, rest = hlo_text.partition(" = ")
    shape = re.match(r"[^{ ]+", rest)
    return f"{module} {name.lstrip('%')} {shape.group(0) if shape else ''}"\
        .strip()


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def find_xplane(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


Event = Tuple[float, float, str]
Chip = Tuple[List[Event], List[Event]]        # (XLA Ops, XLA Modules)


def read_xplane(path: str) -> Tuple[List[Event], List[Chip]]:
    """The ``bench.*`` host annotations and, per chip in device order,
    its op and program events (nanoseconds)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: List[Event] = []
    chips = []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            mods = _events(lines["XLA Modules"]) \
                if "XLA Modules" in lines else []
            chips.append((plane.name, (_events(lines["XLA Ops"]), mods)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(ev for ev in _events(ln)
                            if ev[2].startswith("bench."))
    chips.sort(key=lambda c: int(re.sub(r"\D", "", c[0]) or 0))
    return host, [c for _, c in chips]


def reduce_trace(path: str, top: int = 10) -> Optional[TraceSummary]:
    """The summary of one trace file (see :func:`summarize`)."""
    return summarize(*read_xplane(path), top=top)


def summarize(host: List[Event], chips: List[Chip],
              top: int = 10) -> Optional[TraceSummary]:
    """``None`` where the trace holds no ``bench.window`` or no chip
    (a CPU rehearsal)."""
    windows = [(a, b) for a, b, n in host if n == WINDOW]
    if not windows or not chips:
        return None
    lo, hi = windows[0]
    spans = sorted((a, b, n) for a, b, n in host if n != WINDOW)

    busy_total = 0.0
    per_op: Dict[str, float] = {}
    gaps_of_chip0: List[Interval] = []
    for i, (ops, mods) in enumerate(chips):
        mods = sorted(mods)
        starts = [m[0] for m in mods]
        clipped = []
        for a, b, name in ops:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            clipped.append((a, b))
            j = bisect.bisect_right(starts, a) - 1
            module = mods[j][2].split("(")[0] if j >= 0 and \
                mods[j][1] >= a else "?"
            label = op_label(module, name)
            per_op[label] = per_op.get(label, 0.0) + (b - a)
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        if i == 0:
            gaps_of_chip0 = _gaps(busy, lo, hi)
    idle: Dict[str, float] = {}
    span_starts = [s[0] for s in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)
    for a, b in gaps_of_chip0:
        mid = (a + b) / 2
        holders = []
        j = bisect.bisect_right(span_starts, mid) - 1
        while j >= 0 and spans[j][0] >= mid - longest:
            if spans[j][1] >= mid:
                holders.append(spans[j])
            j -= 1
        name = min(holders, key=lambda s: s[1] - s[0])[2] if holders \
            else NO_SPAN
        idle[name] = idle.get(name, 0.0) + (b - a)

    n = len(chips)
    ops = sorted(((k, v / n / 1e9) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(((k, v / 1e9) for k, v in idle.items()),
                  key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=busy_total / n / 1e9, chips=n,
                        device_ops=ops, idle_gaps=gaps)
