"""One client runs whole jobs back to back.

A job ingests the configuration's data (``MaRe.from_source`` over the
data held in memory, a new rotation of its records each job), runs the
traffic's ``pipeline`` and collects the result to the host (``collect``
holds the keyword arguments of ``collect()``). One job runs in set-up;
no job starts after the window's seconds have passed. The answers of a
seeded sample of ``check_sample`` jobs are kept for the comparison.
"""
from __future__ import annotations

import time
from typing import List

from bench.drive import Action, Sample, build, closed_loop
from repro.core import MaRe


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx

    def job(self, name: str, rotate: int) -> Action:
        ctx, traffic = self.ctx, self.ctx.traffic
        act = Action(name=name, t0=time.perf_counter(),
                     bases=ctx.data.bases)
        with ctx.annotate("bench.ingest"):
            handle = MaRe.from_source(ctx.source(name, rotate),
                                      mesh=ctx.mesh)
        with ctx.annotate("bench.build"):
            chain = build(handle, traffic["pipeline"])
        with ctx.annotate("bench.collect"):
            out = chain.collect(**traffic.get("collect", {}))
        act.t1 = time.perf_counter()
        ctx.finish(act, chain, out, traffic["answer"])
        return act

    def setup(self) -> List[Action]:
        return [self.job("warmup", 0)]

    def window(self, seconds: float, run) -> None:
        ctx = self.ctx
        sample = Sample(int(ctx.traffic.get("check_sample", 1 << 30)),
                        ctx.seed)

        def step(i: int) -> Action:
            act = self.job(f"job{i}", int(ctx.rng.integers(1, ctx.data.n)))
            sample.offer(act)
            return act

        closed_loop(run, seconds, step)
