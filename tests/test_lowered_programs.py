"""The programs of one-word k-mer jobs lower to the same text as at commit
9fd8d13 (before two-word keys and the sorted keyed stage existed): the
k-mer cells' shapes at k = 6 and 12 on 1, 2 and 4 CPU devices, lowered
in a child process (``tests/distributed/lowered_programs.py``), as the
main process stays 1-device. A change that means to alter one of these
programs records its new digest here, with the reason."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)

#: sha256 of each program's lowered StableHLO text at 9fd8d13.
AT_9FD8D13 = {
    "k6.d1": "132142b74ed65e11f21c5450f0c343234b94557eb6935d2392548585c21edb04",
    "k6.d2": "5e20920d050e2d1a87dd8b1eba1eecfc245a8ac7b405cd806d2ba8e2afd953a1",
    "k6.d4": "17513914ab1a11a97337e0346693a40da6ae592ff570d8ab1afb243a687f0265",
    "k12.d1": "33b8e6f4ea71f9fe74ff9678fadae34ddacc6ac14f02824947764d1dd95d1364",
    "k12.d2": "316e8c7a09d1701f9ff2b6933c065b4923997e1c8233aa3951960e92464a941d",
    "k12.d4": "86edcc32dcf3c717b84392d2b1a9c4c15221bf7179ba6f929dc8c9eb543c5f8a",
}


@pytest.fixture(scope="module")
def digests():
    env = dict(os.environ, PYTHONPATH=os.path.abspath(
        os.path.join(HERE, "..", "src")))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "distributed",
                                      "lowered_programs.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", sorted(AT_9FD8D13))
def test_one_word_kmer_program_is_unchanged(program, digests):
    assert digests[program] == AT_9FD8D13[program]
