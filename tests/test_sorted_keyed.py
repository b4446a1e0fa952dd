"""Two-word keys: ``kmer-stats`` for 16 <= k <= 31 and ``canonical``,
the sorted keyed stage that folds them (on one device, and on a 4-device
mesh in a child process, ``tests/distributed/sorted_keyed.py``, as the
main process stays 1-device), ``kmer-histo`` and the whole k-mer
spectrum, each against a host reference: a group-by over NumPy
``uint64`` codes."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import compat
from repro.core import MaRe, PlanCache, PlanTypeError, from_host

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "distributed"))
from sorted_keyed import CASES, MODES  # noqa: E402

_BASES = {ord("A"): 0, ord("C"): 1, ord("G"): 2, ord("T"): 3}


# -- host reference ----------------------------------------------------------

def host_kmer_codes(data, lens, k, canonical):
    """``uint64`` code of every window of ``k`` bases with no ``N``, read
    by read: first base most significant; ``canonical`` the lesser of the
    code and its reverse complement's."""
    out = []
    for row, n in zip(data, lens):
        seq = bytes(row[:n]).upper()
        for i in range(n - k + 1):
            window = seq[i:i + k]
            if any(b not in _BASES for b in window):
                continue
            fwd = rc = 0
            for j, b in enumerate(window):
                fwd = fwd * 4 + _BASES[b]
                rc |= (3 - _BASES[b]) << (2 * j)
            out.append(min(fwd, rc) if canonical else fwd)
    return np.array(out, np.uint64)


def host_groupby(codes, vals):
    """``(distinct codes, summed values, counts)``, codes ascending; sums
    wrap in the values' dtype."""
    uniq, inv = np.unique(codes, return_inverse=True)
    sums = np.array([vals[inv == i].sum() for i in range(uniq.size)])
    return (uniq, sums.astype(vals.dtype),
            np.bincount(inv, minlength=uniq.size))


def as_u64(keys):
    """``[n, 2]`` (high, low) words, or one-word keys, as ``uint64``."""
    keys = np.asarray(keys)
    if keys.ndim == 1:
        return keys.astype(np.uint64)
    return (keys[:, 0].astype(np.uint64) << np.uint64(32)) \
        | keys[:, 1].astype(np.uint64)


def host_spectrum(codes, high):
    counts = np.unique(codes, return_counts=True)[1]
    return np.bincount(np.minimum(counts, high), minlength=high + 1)


# -- inputs ------------------------------------------------------------------

def reads(n=48, width=64, seed=0):
    """Seeded reads of A/C/G/T/N/lower-case a, of random lengths, zero
    padded to ``width``."""
    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(b"ACGTNa", np.uint8)
    data = alphabet[rng.choice(6, size=(n, width),
                               p=[.24, .24, .24, .23, .03, .02])].copy()
    lens = rng.integers(8, width + 1, n).astype(np.int32)
    for i in range(n):
        data[i, lens[i]:] = 0
    return data, lens


def _mesh():
    return compat.make_mesh((1,), ("data",))


def field0(recs):
    return recs[0]


def field1(recs):
    return (recs[1],)


def _kmers(data, lens, **params):
    return MaRe(from_host({"data": data, "len": lens}, _mesh()),
                plan_cache=PlanCache()).map(image="kmer-stats", **params)


# -- kmer-stats --------------------------------------------------------------

@pytest.mark.parametrize("canonical", [False, True])
@pytest.mark.parametrize("k", [13, 16, 21, 31])
def test_kmer_stats_matches_host_reference(k, canonical):
    data, lens = reads(seed=k)
    m = _kmers(data, lens, k=k, canonical=canonical)
    codes = host_kmer_codes(data, lens, k, canonical)
    if k <= 15:
        # one int32 code a window (a dense table of 4**13 keys would
        # only slow the test): the windows themselves, in read order
        got, ones = m.collect()
        assert got.dtype == np.int32
        assert np.array_equal(got.astype(np.uint64), codes)
        assert np.array_equal(ones, np.ones_like(ones))
        return
    q = m.reduce_by_key(field0, value_by=field1, op="sum")
    keys, (sums,), counts = q.collect()
    assert keys.shape[1:] == (2,) and keys.dtype == np.uint32
    uniq, n = np.unique(codes, return_counts=True)
    got = as_u64(keys)
    assert np.array_equal(got, uniq)                 # ascending already
    assert np.array_equal(sums, n) and np.array_equal(counts, n)
    d = q.report().diagnostics
    assert d["stage1.sorted_keyed"] == 1
    assert d["stage1.distinct_keys"] == uniq.size


def test_kmer_stats_grammar_takes_k_and_canonical():
    data, lens = reads(seed=3)
    by_grammar = _kmers(data, lens, command="kmer-stats 21 canonical")
    by_params = _kmers(data, lens, k=21, canonical=True)
    a, b = by_grammar.collect(), by_params.collect()
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    with pytest.raises(ValueError, match="canonical"):
        _kmers(data, lens, command="kmer-stats 21 backwards")


def test_kmer_stats_refuses_k_beyond_31():
    data, lens = reads(seed=4)
    with pytest.raises(ValueError, match="1 <= k <= 31"):
        _kmers(data, lens, k=32).collect()


def test_two_word_key_space_is_undeclared():
    data, lens = reads(seed=5)
    states = _kmers(data, lens, k=21)._stage_states()
    assert states[-1].key_space is None
    assert states[-1].schema.describe() == "(u32[2], i32)"
    assert _kmers(data, lens, k=15)._stage_states()[-1].key_space == 4 ** 15


# -- the sorted keyed stage --------------------------------------------------

@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    """The 4-device results of every case, and the facts of its
    combiner-on program, from one child process."""
    out = tmp_path_factory.mktemp("sorted_keyed")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(
        os.path.join(HERE, "..", "src")))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "distributed",
                                      "sorted_keyed.py"), str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-4000:]
    return out


@pytest.mark.parametrize("case", CASES)
def test_sorted_stage_matches_groupby_on_one_device_and_mesh(case,
                                                             four_devices):
    mode = case.split("-")[-1]
    got4 = np.load(four_devices / f"{case}.npz")
    keys, vals = got4["keys"], got4["vals"]
    capacity = 2 if keys.shape[0] == 0 else None
    q = MaRe(from_host((keys, vals), _mesh(), capacity=capacity),
             plan_cache=PlanCache()).reduce_by_key(
        field0, value_by=field1, **MODES[mode])
    out_keys, (out_vals,), out_counts = q.collect()
    want = host_groupby(as_u64(keys), vals)
    got1 = (as_u64(out_keys), out_vals, out_counts)
    order = np.argsort(as_u64(got4["out_keys"]), kind="stable")
    got4 = (as_u64(got4["out_keys"])[order], got4["out_vals"][order],
            got4["out_counts"][order])
    for got in (got1, got4):
        assert [a.size for a in got] == [b.size for b in want], case
        for a, b in zip(got, want):
            assert np.array_equal(a, b.astype(a.dtype)), case
    assert out_keys.dtype == np.uint32 and out_vals.dtype == vals.dtype
    d = q.report().diagnostics
    assert d["stage0.sorted_keyed"] == d["stage0.local_keyed"] == 1
    assert d["stage0.distinct_keys"] == want[0].size


def test_sorted_stage_scopes_and_counters(four_devices):
    data = (np.array([[1, 2], [1, 2], [3, 4], [0, 2]], np.uint32),
            np.array([5, 6, 7, 8], np.int32))
    q = MaRe(from_host(data, _mesh()), plan_cache=PlanCache()
             ).reduce_by_key(field0, value_by=field1)
    assert "keys=sorted" in q.describe()
    assert "stage0.distinct_keys" in q.describe()
    keys, (sums,), counts = q.collect()
    assert keys.tolist() == [[0, 2], [1, 2], [3, 4]]
    assert sums.tolist() == [8, 11, 7] and counts.tolist() == [1, 2, 1]
    (prog,) = q.plan_cache.programs()
    scopes = set(prog.op_scopes().values())
    assert "s0.reduce_by_key/combine" in scopes
    assert not [s for s in scopes if s.endswith(("/exchange", "/merge"))]
    assert prog.sorted_keyed == {0: 1}
    d = q.report().diagnostics
    assert (d["stage0.distinct_keys"], d["stage0.exchanged_records"],
            d["stage0.shuffle_dropped"]) == (3, 3, 0)
    assert "stage0.key_overflow" not in d
    facts = json.loads((four_devices / "facts.json").read_text())
    assert {"s0.reduce_by_key/combine", "s0.reduce_by_key/exchange",
            "s0.reduce_by_key/merge"} <= set(facts["scopes"])
    assert (facts["sorted_keyed"], facts["local_keyed"],
            facts["shuffle_dropped"]) == (1, 0, 0)
    mixed = np.load(four_devices / "mixed-sum-int32-combiner.npz")
    assert facts["distinct_keys"] == np.unique(as_u64(mixed["keys"])).size


def test_sorted_stage_refuses_num_keys_salt_and_non_sums():
    data = (np.zeros((8, 2), np.uint32), np.ones(8, np.int32))
    m = MaRe(from_host(data, _mesh()), plan_cache=PlanCache())
    with pytest.raises(ValueError, match="one-word key only"):
        m.reduce_by_key(field0, value_by=field1, num_keys=16)
    with pytest.raises(ValueError, match="dense"):
        m.reduce_by_key(field0, value_by=field1, combiner=False, salt=4)
    with pytest.raises(ValueError, match="op='sum'"):
        m.reduce_by_key(field0, value_by=field1, op="max")
    floats = (data[0], np.ones(8, np.float32))
    with pytest.raises(PlanTypeError, match="integer values only"):
        MaRe(from_host(floats, _mesh()), plan_cache=PlanCache()
             ).reduce_by_key(field0, value_by=field1)


def test_one_word_key_without_key_space_still_needs_num_keys():
    data = (np.zeros(8, np.int32), np.ones(8, np.int32))
    m = MaRe(from_host(data, _mesh()), plan_cache=PlanCache())
    with pytest.raises(ValueError, match="num_keys not given"):
        m.reduce_by_key(field0, value_by=field1)


def test_key_of_three_words_is_a_plan_type_error():
    data = (np.zeros((8, 3), np.uint32), np.ones(8, np.int32))
    m = MaRe(from_host(data, _mesh()), plan_cache=PlanCache())
    with pytest.raises(PlanTypeError, match=r"shape \[8\]"):
        m.reduce_by_key(field0, value_by=field1, num_keys=4)


# -- kmer-histo and the spectrum ---------------------------------------------

def test_kmer_histo_bins_counts_and_folds_the_top():
    keys = np.array([[0, 1], [0, 2], [0, 3], [0, 4], [0, 5]], np.uint32)
    counts = np.array([1, 3, 3, 7, 9], np.int32)
    m = MaRe(from_host((keys, (counts,), counts), _mesh()),
             plan_cache=PlanCache()).map(image="kmer-histo", high=5)
    assert m._stage_states()[-1].key_space == 6
    q = m.reduce_by_key(field0, value_by=field1)
    bins, (sums,), n = q.collect()
    assert bins.tolist() == [1, 3, 5] and sums.tolist() == [1, 2, 2]
    assert np.array_equal(sums, n)


@pytest.mark.parametrize("k", [16, 21])
def test_spectrum_pipeline_matches_host_reference(k):
    data, lens = reads(n=96, width=48, seed=k + 100)
    # a read of no N repeated: k-mers seen more than `high` times
    data[:40] = np.frombuffer(b"ACGT", np.uint8)[
        np.random.default_rng(k).integers(0, 4, 48)]
    lens[:40] = 48
    high = 20
    q = (_kmers(data, lens, k=k, canonical=True)
         .reduce_by_key(field0, value_by=field1)
         .map(image="kmer-histo", high=high)
         .reduce_by_key(field0, value_by=field1))
    bins, (sums,), counts = q.collect()
    want = host_spectrum(host_kmer_codes(data, lens, k, True), high)
    got = np.zeros(high + 1, np.int64)
    got[bins] = sums
    assert np.array_equal(got, want) and np.array_equal(sums, counts)
    assert want[high] > 0
