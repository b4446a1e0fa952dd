"""What the spectrum cell's metrics share: which stage of the job's program
is the sorted keyed stage, device seconds by stage relative to it, and
the least bytes any implementation of that stage moves.

The stage is the one whose ``stage<i>.sorted_keyed`` diagnostic is 1
(``repro.runtime.executor``); a program without the diagnostic has no
such stage, and the readers then return ``None``.
"""
from __future__ import annotations

import re
from typing import Callable, Optional

from bench import scopes

#: Bytes of one record of the stage: a two-word key and an int32 value.
RECORD_BYTES = 12


def sorted_stage(run) -> Optional[int]:
    """Index of the sorted keyed stage in the jobs' program."""
    for a in run.done:
        for k, v in a.counters.items():
            m = re.fullmatch(r"stage(\d+)\.sorted_keyed", k)
            if m and v == 1:
                return int(m.group(1))
    return None


def stage_seconds(run, pick: Callable[[int, str], bool]
                  ) -> Optional[float]:
    """Device seconds per job under the scopes ``s<j>.<kind>[/part]`` for
    which ``pick(j - i, kind)`` holds, ``i`` the sorted stage."""
    i = sorted_stage(run)
    if i is None:
        return None

    def match(scope: str) -> bool:
        m = re.match(r"s(\d+)\.([a-z_]+)", scope)
        return m is not None and pick(int(m.group(1)) - i, m.group(2))

    return scopes.scope_seconds(run, match)


def distinct_keys(run) -> Optional[float]:
    """Mean ``stage<i>.distinct_keys`` per job, summed over stages."""
    per_job = [sum(v for k, v in a.counters.items()
                   if k.endswith(".distinct_keys")) for a in run.done
               if any(k.endswith(".distinct_keys") for k in a.counters)]
    return sum(per_job) / len(per_job) if per_job else None


def sortagg_bytes(windows: int, distinct: float) -> float:
    """The least bytes the stage moves: every valid window's record read
    once and every distinct key's record written once."""
    return (windows + distinct) * RECORD_BYTES


def spectrum_windows(run) -> Optional[int]:
    """Valid windows of a job, ``sum of b x sums[b]`` of a kept spectrum
    answer (``bench/references/kmer_spectrum.py``); k-mers seen ``high``
    times or more count ``high`` times, so it is a lower bound."""
    for a in run.done:
        if a.answer is not None and \
                a.answer_spec.get("reference") == "kmer_spectrum":
            bins, sums, _ = a.answer
            return int(sum(int(b) * int(s) for b, s in zip(bins, sums)))
    return None
