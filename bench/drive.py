"""What every traffic loop shares: the record of a run, the building of a
pipeline from a traffic file, and the loading of a loop by name.

A traffic file (``bench/traffic/<name>.json``) holds parameters only. Its
``loop`` names a module ``bench/loops/<loop>.py`` whose ``Loop(ctx)``
has ``setup()``, which warms up every shape the window uses and returns
those actions, and ``window(seconds, run)``, which drives the cell and
appends every action to ``run.actions``.

A ``pipeline`` is a list of ``{"<primitive>": {keyword arguments}}``
steps applied to a ``MaRe`` handle (``"$key"`` values are filled in from
the configuration by ``bench.spec.traffic``). ``key_by`` and
``value_by`` name a selector of :data:`SELECTORS`. An ``answer`` names
its reference, ``bench/references/<reference>.py``, with the reference's
parameters: the reference turns the collected output into the answer
that ``bench.check`` compares.
"""
from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import spec


def field0(recs):
    return recs[0]


def field1(recs):
    return (recs[1],)


#: Named record selectors for ``reduce_by_key`` (named functions, so the
#: program's compile cache sees the same callable every time).
SELECTORS: Dict[str, Callable] = {"field0": field0, "field1": field1}


@dataclasses.dataclass
class Action:
    """One job or query: host-clock start and end, the executor's phase
    split and counters, and its answer with the ``answer`` entry of the
    traffic file that says how to check it."""

    name: str
    t0: float
    t1: float = 0.0
    bases: int = 0
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    answer: Any = None
    answer_spec: Optional[Dict[str, Any]] = None
    error: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """Everything one run recorded; the metric readers read this."""

    setup_s: float = 0.0
    window_t0: float = 0.0
    setup_actions: List[Action] = dataclasses.field(default_factory=list)
    actions: List[Action] = dataclasses.field(default_factory=list)
    peak_bytes: List[int] = dataclasses.field(default_factory=list)
    spans: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    trace: Any = None

    @property
    def done(self) -> List[Action]:
        return [a for a in self.actions if a.error is None]

    @property
    def window_s(self) -> float:
        """Window start to the end of the last action."""
        ends = [a.t1 for a in self.actions]
        return (max(ends) if ends else self.window_t0) - self.window_t0


def build(handle, pipeline: List[Dict[str, Any]]):
    for step in pipeline:
        ((prim, kwargs),) = step.items()
        kwargs = dict(kwargs)
        for sel in ("key_by", "value_by"):
            if sel in kwargs:
                kwargs[sel] = SELECTORS[kwargs[sel]]
        handle = getattr(handle, prim)(**kwargs)
    return handle


class Sample:
    """Seeded reservoir of at most ``size`` answers, for answers too large
    to keep for every action: an answer that leaves it is dropped."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng([seed, 2])
        self.held: List[Action] = []
        self.seen = 0

    def offer(self, action: Action) -> None:
        self.seen += 1
        if len(self.held) < self.size:
            self.held.append(action)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.held[j].answer = None
            self.held[j] = action
        else:
            action.answer = None


@dataclasses.dataclass
class Context:
    """What a loop is given: its traffic file (resolved), the cell's data
    (``bench/gen/<data>.py``), the device mesh, the seed (``rng`` draws
    the loop's choices from it), and ``annotate``, which names a span of
    host work in the profiler's trace."""

    traffic: Dict[str, Any]
    data: Any
    mesh: Any
    seed: int
    annotate: Callable[[str], Any]

    def __post_init__(self) -> None:
        self.rng = np.random.default_rng([self.seed, 1])

    def source(self, name: str, rotate: int = 0):
        """A ``repro.io`` source over the data, rotated by ``rotate``
        records, split as the traffic file says."""
        return self.data.source(name, int(self.traffic["split_bytes"]),
                                rotate)

    def finish(self, action: Action, chain, out: Any,
               answer_spec: Dict[str, Any]) -> None:
        """Record the chain's report and the answer of ``out``."""
        rep = chain.report()
        action.phases = dict(rep.phases)
        action.counters = dict(rep.diagnostics)
        action.answer_spec = answer_spec
        action.answer = spec.module(
            "references", answer_spec["reference"]).answer(out)


def closed_loop(run: Run, seconds: float, step: Callable[[int], Action]
                ) -> None:
    """``step(0)``, ``step(1)``, ... one after another until ``seconds``
    have passed since the window opened. An action that raises is
    recorded as failed, not hidden, and its traceback goes to stderr."""
    deadline = run.window_t0 + seconds
    i = 0
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        try:
            act = step(i)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            act = Action(name=f"action{i}", t0=t0, t1=time.perf_counter(),
                         error=f"{type(e).__name__}: {e}")
        run.actions.append(act)
        i += 1


def load_loop(ctx: Context):
    """The loop that the traffic file names, built over ``ctx``."""
    return spec.module("loops", ctx.traffic["loop"]).Loop(ctx)
