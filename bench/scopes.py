"""Device seconds per program scope, and chip idle per program span, from
the window's profile (``.bench_trace``).

The program names its compiled work (``repro.core.planner``): each
program is ``jit_<name>`` in the profile's ``XLA Modules`` events, and
``CompiledProgram.op_scopes()`` maps each of its HLO ops to the scope it
ran under (``s0.map``, ``s1.reduce_by_key/combine``, ...). While tracing
is on, its spans (``repro.obs``) are profiler annotations on the host
plane, on the device's clock. So:

* scope seconds: each op of the window, clipped to it, counts under its
  program's scope for it (``unscoped`` where it has none; ``other`` for
  ops of a program that is not in the process's plan cache), summed and
  averaged over the chips;
* idle by span: each stretch of the window in which chip 0 ran no op is
  split, by overlap, across the innermost program span open at each
  moment on the thread that holds ``bench.window``; ``unspanned`` where
  none is open.

Where the run has no chip plane (a CPU rehearsal), or the program does
not name its ops or put its spans in the profile (an older program),
the readers return ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from typing import Callable, Dict, List, Optional, Tuple

from bench.run import TRACE_DIR
from bench.trace import (WINDOW, Chip, Event, Interval, _events, _gaps,
                         _union, find_xplane)

#: ``repro.obs.UNSCOPED``, spelled out so that this module also loads
#: beside a program whose ``repro.obs`` has no op scopes.
UNSCOPED = "unscoped"
OTHER = "other"
UNSPANNED = "unspanned"


@dataclasses.dataclass
class Profile:
    window: Interval                 # bench.window, nanoseconds
    thread: List[Event]              # every event on the window's thread
    chips: List[Chip]                # per chip: (XLA Ops, XLA Modules)


@functools.lru_cache(maxsize=2)
def read_profile(path: str) -> Optional[Profile]:
    """The window, its thread's host events and each chip's op and
    program events of one ``.xplane.pb``; ``None`` without a window or
    a chip."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, thread, chips = None, [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            mods = _events(lines["XLA Modules"]) \
                if "XLA Modules" in lines else []
            chips.append((plane.name, (_events(lines["XLA Ops"]), mods)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                events = _events(ln)
                for a, b, name in events:
                    if name == WINDOW and window is None:
                        window, thread = (a, b), events
    if window is None or not chips:
        return None
    chips.sort(key=lambda c: int(re.sub(r"\D", "", c[0]) or 0))
    return Profile(window, thread, [c for _, c in chips])


def _profile(run) -> Optional[Profile]:
    if run.trace is None:            # no window or no chip in the trace
        return None
    path = find_xplane(str(TRACE_DIR))
    return read_profile(path) if path else None


def program_scopes() -> Optional[Dict[str, Dict[str, str]]]:
    """``{module name: {HLO op: scope}}`` of every compiled program in the
    process's plan cache; ``None`` where programs name no scopes."""
    from repro.core import DEFAULT_CACHE
    out = {}
    for prog in DEFAULT_CACHE.programs():
        scopes = getattr(prog, "op_scopes", None)
        if scopes is None:
            continue
        try:
            out[f"jit_{prog.name}"] = scopes()
        except RuntimeError:         # built but never compiled
            continue
    return out or None


def scope_totals(chips: List[Chip], window: Interval,
                 scopes: Dict[str, Dict[str, str]]) -> Dict[str, float]:
    """Seconds per scope of the ops inside ``window``, averaged over the
    chips. An op's program is the ``XLA Modules`` event that holds its
    start; its HLO name is the event name before `` = ``. Where ops nest
    (a loop and its body), each moment counts under the innermost, so
    a chip's scopes sum to its busy time."""
    lo, hi = window
    total: Dict[str, float] = {}
    for ops, mods in chips:
        mods = sorted(mods)
        starts = [m[0] for m in mods]
        named: List[Event] = []
        for a, b, text in ops:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            j = bisect.bisect_right(starts, a) - 1
            module = mods[j][2].split("(")[0] if j >= 0 and \
                mods[j][1] >= a else "?"
            if module in scopes:
                op = text.partition(" = ")[0].strip().lstrip("%")
                named.append((a, b, scopes[module].get(op, UNSCOPED)))
            else:
                named.append((a, b, OTHER))
        for a, b, scope in _innermost(named):
            total[scope] = total.get(scope, 0.0) + (b - a)
    n = max(len(chips), 1)
    return {k: v / n / 1e9 for k, v in total.items()}


def _innermost(spans: List[Event]) -> List[Event]:
    """Disjoint stretches, each named by the innermost span open in it
    (spans on one thread, or ops on one core, nest)."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    points = sorted({t for a, b, _ in spans for t in (a, b)})
    out: List[Event] = []
    stack: List[Tuple[float, str]] = []
    i = 0
    for x, y in zip(points, points[1:]):
        while i < len(order) and order[i][0] <= x:
            a, b, name = order[i]
            while stack and stack[-1][0] <= a:
                stack.pop()
            stack.append((b, name))
            i += 1
        while stack and stack[-1][0] <= x:
            stack.pop()
        if stack:
            out.append((x, y, stack[-1][1]))
    return out


def split_idle(gaps: List[Interval], spans: List[Event]
               ) -> Dict[str, float]:
    """Each gap's length split, by overlap, across the innermost span open
    at each moment; :data:`UNSPANNED` where none is. Same unit as the
    intervals."""
    out: Dict[str, float] = {}
    pieces = _innermost(spans)
    ends = [p[1] for p in pieces]
    for a, b in gaps:
        covered = 0.0
        j = bisect.bisect_right(ends, a)
        while j < len(pieces) and pieces[j][0] < b:
            x, y, name = pieces[j]
            lap = min(b, y) - max(a, x)
            if lap > 0:
                out[name] = out.get(name, 0.0) + lap
                covered += lap
            j += 1
        if b - a > covered:
            out[UNSPANNED] = out.get(UNSPANNED, 0.0) + (b - a - covered)
    return out


def idle_totals(profile: Profile, names: set) -> Optional[Dict[str, float]]:
    """Chip 0's idle seconds in the window per innermost program span
    (events named in ``names`` on the window's thread); ``None`` where no
    such span is in the profile."""
    lo, hi = profile.window
    spans = [(max(a, lo), min(b, hi), n) for a, b, n in profile.thread
             if n in names and min(b, hi) > max(a, lo)]
    if not spans:
        return None
    ops, _ = profile.chips[0]
    busy = _union([(max(a, lo), min(b, hi)) for a, b, _ in ops
                   if min(b, hi) > max(a, lo)])
    return {k: v / 1e9 for k, v in
            split_idle(_gaps(busy, lo, hi), spans).items()}


def per_job(run, seconds: Optional[float]) -> Optional[float]:
    """``seconds`` over the window's jobs that completed."""
    done = len(run.done)
    return seconds / done if seconds is not None and done else None


def scope_seconds(run, match: Callable[[str], bool]) -> Optional[float]:
    """Device seconds per job under the scopes that ``match`` accepts."""
    profile = _profile(run)
    scopes = program_scopes() if profile is not None else None
    if scopes is None:
        return None
    totals = scope_totals(profile.chips, profile.window, scopes)
    return per_job(run, sum(v for k, v in totals.items() if match(k)))


def idle_seconds(run, name: str) -> Optional[float]:
    """Chip 0's idle seconds per job that fall to the span ``name``, or
    to none (:data:`UNSPANNED`)."""
    profile = _profile(run)
    if profile is None:
        return None
    names = {e["name"] for e in run.spans if e.get("ph") == "X"}
    idle = idle_totals(profile, names)
    return per_job(run, idle.get(name, 0.0)) if idle is not None else None
