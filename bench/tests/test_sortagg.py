"""The spectrum cell's readers on hand-made runs: which stage is sorted,
the distinct keys and valid windows a job reports, the stage's least
bytes, and nothing to read from a program without the stage."""
import numpy as np

from bench import sortagg
from bench.drive import Action, Run


def _run(counters, answer=None):
    run = Run()
    for i, c in enumerate(counters):
        act = Action(name=f"job{i}", t0=0.0, t1=1.0, counters=dict(c))
        if i == 0 and answer is not None:
            act.answer = answer
            act.answer_spec = {"reference": "kmer_spectrum", "k": 21,
                               "canonical": True, "high": 10000}
        run.actions.append(act)
    return run


SPECTRUM_JOB = {"stage1.sorted_keyed": 1, "stage3.sorted_keyed": 0,
                "stage1.distinct_keys": 17, "stage1.local_keyed": 1}


def test_sorted_stage_distinct_keys_and_windows():
    answer = (np.array([1, 2, 5]), np.array([10, 4, 1]),
              np.array([10, 4, 1]))
    run = _run([SPECTRUM_JOB, {**SPECTRUM_JOB,
                               "stage1.distinct_keys": 19}], answer)
    assert sortagg.sorted_stage(run) == 1
    assert sortagg.distinct_keys(run) == 18.0
    assert sortagg.spectrum_windows(run) == 1 * 10 + 2 * 4 + 5 * 1
    assert sortagg.sortagg_bytes(23, 18.0) == (23 + 18) * 12


def test_a_program_without_the_stage_reads_nothing():
    run = _run([{"stage1.local_keyed": 1, "stage1.exchanged_records": 9}])
    assert sortagg.sorted_stage(run) is None
    assert sortagg.distinct_keys(run) is None
    assert sortagg.spectrum_windows(run) is None
    assert sortagg.stage_seconds(run, lambda d, kind: d == 0) is None
