"""A spectrum job whose timed path is broken underneath comes out not
correct: each fault planted under ``kmer21.spectrum`` at its rehearsal
size on the CPU.

This file is also the script that plants them:

    python bench/tests/test_spectrum_faults.py <fault> --workload \
        kmer21.spectrum --seed 5 --seconds 1 --trace 0 --rehearse

* ``dropcount``: the sorted keyed stage hands on its first distinct
  key with a record count of 0;
* ``forward``: ``kmer-stats`` counts forward codes where canonical ones
  are asked for;
* ``lastseg``: the sorted keyed stage loses its last segment (its last
  distinct key).
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import pytest  # noqa: E402

from bench.tests.harness import cell_args, result, run  # noqa: E402

FAULTS = ["dropcount", "forward", "lastseg"]


def plant(fault: str) -> None:
    import repro.core.planner as planner
    real = planner.sort_aggregate
    if fault == "dropcount":
        def dropped(*args):
            agg = real(*args)
            return agg._replace(counts=agg.counts.at[0].set(0))

        planner.sort_aggregate = dropped
    elif fault == "forward":
        import repro.core.images as images
        windows = images._kmer_windows

        def forward(code, k, canonical):
            return windows(code, k, False)

        images._kmer_windows = forward
    elif fault == "lastseg":
        def short(*args):
            agg = real(*args)
            return agg._replace(distinct=agg.distinct - 1)

        planner.sort_aggregate = short
    else:
        raise SystemExit(f"unknown fault {fault!r}")


@pytest.mark.parametrize("fault", FAULTS)
def test_spectrum_fault_is_not_correct(fault):
    out = result(run(fault, *cell_args("kmer21.spectrum"),
                     script=Path(__file__)))
    assert out["correct"] is False
    assert out["failed"] == 0            # wrong answers, not crashes
    assert out["checks"]["wrong_spectrum_bins"]["value"] > 0


if __name__ == "__main__":
    plant(sys.argv[1])
    from bench.run import main
    sys.exit(main(sys.argv[2:]))
