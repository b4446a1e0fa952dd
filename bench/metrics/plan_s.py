"""Seconds of ``plan.lower`` and ``plan.compile`` (executor phases) over
the set-up's warm-up actions: tracing and lowering, and compilation or
its load from the persistent compile cache."""


def read(run):
    total = sum(a.phases.get(p, 0.0) for a in run.setup_actions
                for p in ("plan.lower", "plan.compile"))
    return total if run.setup_actions else None
