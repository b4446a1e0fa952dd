"""One analyst queries one resident dataset in a closed loop.

The configuration's data is ingested once in set-up and stays on the
device. Each of the traffic's ``queries`` (a ``name``, a ``pipeline``,
the keyword arguments of ``collect()`` and an ``answer``) runs once in
set-up; the window runs them one after another, in an order drawn from
the seed and repeated. No query starts after the window's seconds have
passed.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

from bench.drive import Action, build, closed_loop
from repro.core import MaRe


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.queries = ctx.traffic["queries"]

    def query(self, q: Dict[str, Any]) -> Action:
        act = Action(name=q["name"], t0=time.perf_counter())
        with self.ctx.annotate("bench.query"):
            chain = build(MaRe(self.resident), q["pipeline"])
            out = chain.collect(**q.get("collect", {}))
        act.t1 = time.perf_counter()
        self.ctx.finish(act, chain, out, q["answer"])
        return act

    def setup(self) -> List[Action]:
        self.resident = MaRe.from_source(self.ctx.source("resident"),
                                         mesh=self.ctx.mesh).dataset
        return [self.query(q) for q in self.queries]

    def window(self, seconds: float, run) -> None:
        order = self.ctx.rng.permutation(len(self.queries))
        closed_loop(run, seconds, lambda i: self.query(
            self.queries[order[i % len(order)]]))
