"""The programs of one-word k-mer jobs lower to pinned text: the k-mer
cells' shapes at k = 6 and 12 on 1, 2 and 4 CPU devices, lowered in a
child process (``tests/distributed/lowered_programs.py``), as the main
process stays 1-device. A change that means to alter one of these
programs records its new digest here, with the reason."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)

#: sha256 of each program's lowered StableHLO text once ``kmer-stats``
#: compacts its windows with one sort that carries the code word, keyed
#: on the invalid flag over the window's index, in place of an argsort
#: and a gather of the word through its order. Against the text before
#: (commit 9fd8d13), only that compaction differs: the map's ``argsort``
#: call and ``_take`` gather went, the key's shift, iota and or and one
#: unstable ``stablehlo.sort`` of the key and the word came, and values
#: and functions were renumbered.
SORT_CARRIES_WORDS = {
    "k6.d1": "b6ac95f3f9800c042249a8c3f768dcb782818296be5e36d14b9686d4d5e88c40",
    "k6.d2": "1cb4af4711a873a9affc93ec972953a5a2cfc380cdf8f54039937d541f95b3c5",
    "k6.d4": "a84458394d1ecda5a5ad40cfffdea8aec818d0fd1006d2c75dfa79f13682b5f1",
    "k12.d1": "b048b3c32c9432c1ac849d782fc134c5570bd1022bb741bc2692707cf881b07b",
    "k12.d2": "0d78e7eda62ed24e2793baaaafa2b43d5275d9fd821e30ce28f000dbbc1a2546",
    "k12.d4": "1161a79df54e601d6e5076820d3ea69b890ccc633fa58fdf77da0f7d711a5ab3",
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


@pytest.mark.parametrize("program", sorted(SORT_CARRIES_WORDS))
def test_one_word_kmer_program_is_unchanged(program, digests):
    assert digests[program] == SORT_CARRIES_WORDS[program]
