"""The controls come out not correct, and the references agree with
their plain definitions, at sizes a test run holds (CPU)."""
import json

import numpy as np
import pytest

from bench import check, spec
from bench.control import control_actions, main
from bench.data import MemoryStore
from bench.drive import Action

make_reads = spec.module("gen", "uniform_reads").make_reads
kmer = spec.module("references", "kmer_table")


def _answered(specs, answers):
    return [Action(f"a{i}", 0.0, answer=a, answer_spec=s)
            for i, (s, a) in enumerate(zip(specs, answers))]


def test_kmer_reference_matches_the_direct_count():
    reads = make_reads(300, 150, 4, seed=2 ** 31 + 11)
    k = 5
    got = kmer.kmer_table(reads.seq, k)
    want = np.zeros(4 ** k, np.int64)
    code = {ord("A"): 0, ord("C"): 1, ord("G"): 2, ord("T"): 3}
    for row in reads.seq:
        for i in range(len(row) - k + 1):
            w = row[i:i + k]
            if ord("N") in w:
                continue
            key = 0
            for b in w:
                key = key * 4 + code[int(b)]
            want[key] += 1
    assert np.array_equal(got, want)


def test_kmer_control_is_not_correct():
    reads = make_reads(2048, 150, 10, seed=4_000_000_019)
    specs = [{"reference": "kmer_table", "k": 12}]
    (table,) = kmer.expected(reads, specs)
    keys = np.flatnonzero(table)
    sound = _answered(specs, [(keys, table[keys], table[keys])])
    assert check.is_correct(check.compare(sound, reads))
    bad = control_actions({"answer": specs[0]}, reads)
    numbers = check.compare(bad, reads)
    assert not check.is_correct(numbers)
    assert numbers["wrong_table_entries"][0] > 0


def test_char_count_control_is_not_correct():
    # over 2**24 bases, so float32 sums can no longer hold every count
    reads = make_reads(240_000, 150, 10, seed=3_000_000_001)
    traffic = {"queries": [
        {"name": n, "answer": {"reference": "char_count", "chars": c}}
        for n, c in (("gc", "GC"), ("acgt", "ACGT"))]}
    specs = check.answer_specs(traffic)
    ref = spec.module("references", "char_count")
    expected = ref.expected(reads, specs)
    seq = reads.seq
    assert expected[0] == int(np.count_nonzero(
        (seq == ord("G")) | (seq == ord("C"))))
    assert check.is_correct(check.compare(_answered(specs, expected), reads))
    numbers = check.compare(control_actions(traffic, reads), reads)
    assert numbers["wrong_answers"][0] >= 1


def test_failed_actions_are_compared():
    reads = make_reads(64, 150, 10, seed=5)
    failed = [Action("a0", 0.0, error="RuntimeError: lost")]
    assert check.compare(failed, reads) == {"failed_actions": (1, 0)}
    assert not check.is_correct(check.compare(failed, reads))


def test_answer_specs_are_found_anywhere_once():
    traffic = {"answer": {"reference": "r", "k": 1},
               "queries": [{"answer": {"reference": "r", "k": 1}},
                           {"answer": {"k": 2, "reference": "r"}}]}
    assert check.answer_specs(traffic) == [{"reference": "r", "k": 1},
                                           {"reference": "r", "k": 2}]


@pytest.mark.parametrize("cell,number", [("kmer12.batch",
                                          "wrong_table_entries"),
                                         ("gc.interactive", "wrong_answers")])
def test_control_script_prints_the_numbers_compared(cell, number, capsys):
    # the rehearsal sizes: the k-mer control fails; the float32 sums of
    # the small interactive set are exact, so its control reads correct
    assert main(["--workload", cell, "--seeds", "7", "--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(out)
    assert number in line["checks"]
    if cell == "kmer12.batch":
        assert line["correct"] is False


def test_memory_store_rotation_keeps_every_read():
    reads = make_reads(100, 150, 10, seed=7)
    store = MemoryStore(reads, "a.fa", rotate=37)
    size = store.size("a.fa")
    whole = store.read_range("a.fa", 0, size)
    rows = np.frombuffer(whole, np.uint8).reshape(reads.lines.shape)
    assert np.array_equal(rows, np.roll(reads.lines, -37, axis=0))
    assert store.read_range("a.fa", 10, 20) == whole[10:20]
    assert store.read_range("a.fa", size - 5, size + 9) == whole[-5:]
