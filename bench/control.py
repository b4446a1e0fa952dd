#!/usr/bin/env python3
"""Read the control of a cell at the cell's own size, on several seeds.

    python bench/control.py --workload kmer12.batch --seeds 11 12 13

The control is the plain reference with one stated guarantee broken, put
in the program's place: each reference of the cell's traffic
(``bench/references/<reference>.py``) answers every ``answer`` entry of
the traffic file with its ``control``, and the run's comparison
(``bench.check``) judges those answers against the reference. Each seed
prints one JSON line with the numbers compared beside their limits:
these are the upper readings of PERF.md. The benchmark's own runs never
run this. ``--rehearse`` takes the configuration's rehearsal sizes, on
any backend.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_actions(traffic, data):
    """One action for each ``answer`` entry of the traffic file, answered
    by its reference's control."""
    from bench import check, spec
    from bench.drive import Action
    actions = []
    for s in check.answer_specs(traffic):
        (got,) = spec.module("references", s["reference"]).control(data, [s])
        actions.append(Action(f"control{len(actions)}", 0.0, answer=got,
                              answer_spec=s))
    return actions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import check, spec as spec_lib
    spec = spec_lib.load()
    cell = spec_lib.workload(spec, args.workload)
    cfg = spec_lib.config(spec, cell["config"])
    if args.rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    traffic = spec_lib.traffic(cell["traffic"], cfg)
    gen = spec_lib.module("gen", cfg["data"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        data = gen.make(cfg, int(cell["chips"]), seed)
        actions = control_actions(traffic, data)
        numbers = check.compare(actions, data)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "answers": len(actions),
            "correct": check.is_correct(numbers),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()},
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
