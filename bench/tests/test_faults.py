"""A run whose timed path is broken underneath comes out not correct:
each fault that a cell can have, at the rehearsal size on the CPU."""
import pytest

from bench.tests.harness import ROOT, cell_args, result, run

FAULTS = [("kmer12.batch", "half"), ("kmer12.batch", "alter"),
          ("gc.interactive", "half"), ("gc.interactive", "alter"),
          ("kmer12.batch.x4", "half"), ("kmer12.batch.x4", "exchange"),
          ("kmer12.batch.x4", "alter")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(cell, fault):
    out = result(run(fault, *cell_args(cell),
                     script=ROOT / "bench" / "tests" / "fault_run.py"))
    assert out["correct"] is False
    assert out["failed"] == 0            # wrong answers, not crashes
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
