"""``kmer-stats`` compacts its valid windows with one sort that carries
the code words, keyed on the invalid flag over the window's index. Its
output equals, bit for bit and padding rows included, the compaction it
replaces: an ``argsort(~ok, stable=True)`` and one gather per word
through that order. The lowered map holds no gather, and its one sort
is not stable (no two keys tie; a stable sort carries an index of its
own on a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_partition, pull
from repro.core.images import KMER_ONE_WORD_K, _BASE_CODES, _kmer_windows

WIDTH = 48
CAP = 64
COUNT = 53

#: (k, canonical): one word up to k = 15, two words beyond.
CASES = [(6, False), (12, False), (16, False), (21, True), (21, False),
         (31, False)]


def argsort_take_kmer_stats(part, k, canonical):
    """``kmer-stats`` with its former compaction: the same windows and
    validity, gathered through ``argsort(~ok, stable=True)``."""
    data, lens = part.records["data"], part.records["len"]
    cap, width = data.shape
    nw = width - k + 1
    upper = jnp.where((data >= 97) & (data <= 122), data - 32, data)
    code = jnp.zeros_like(upper, dtype=jnp.int32)
    base_ok = jnp.zeros(data.shape, bool)
    for byte, c in _BASE_CODES.items():
        hit = upper == byte
        code = jnp.where(hit, c, code)
        base_ok = base_ok | hit
    words = _kmer_windows(code, k, canonical)
    window_ok = jnp.ones((cap, nw), bool)
    for j in range(k):
        window_ok = window_ok & base_ok[:, j:j + nw]
    in_len = jnp.arange(nw)[None, :] + k <= lens[:, None]
    ok = (window_ok & in_len & part.mask()[:, None]).reshape(-1)
    order = jnp.argsort(~ok, stable=True)
    words = [jnp.take(w.reshape(-1), order, mode="clip") for w in words]
    codes = words[0] if len(words) == 1 else jnp.stack(words, axis=1)
    total = jnp.sum(ok).astype(jnp.int32)
    ones = (jnp.arange(cap * nw) < total).astype(jnp.int32)
    return make_partition((codes, ones), total)


def partition(k, seed=0):
    """Seeded reads of upper- and lower-case bases with ``N``/``n``, of
    length 0, 1, k - 1, k, the full width and random lengths, the bytes
    past each length non-zero, and ``COUNT`` < ``CAP`` valid rows (the
    rows past the count hold reads too)."""
    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(b"ACGTacgtNn", np.uint8)
    data = alphabet[rng.choice(alphabet.size, size=(CAP, WIDTH),
                               p=[.2, .2, .2, .2, .04, .04, .04, .04,
                                  .02, .02])]
    lens = rng.integers(0, WIDTH + 1, CAP).astype(np.int32)
    lens[:6] = [0, 1, k - 1, k, WIDTH, WIDTH]
    records = {"data": jnp.asarray(data), "len": jnp.asarray(lens)}
    return make_partition(records, COUNT)


@pytest.mark.parametrize("k,canonical", CASES)
def test_sort_compaction_equals_argsort_take(k, canonical):
    part = partition(k, seed=k + canonical)
    op = pull("kmer-stats", k=k, canonical=canonical)
    got = jax.jit(lambda p: op(p))(part)
    want = jax.jit(lambda p: argsort_take_kmer_stats(p, k, canonical))(part)
    assert 0 < int(want.count) < CAP * (WIDTH - k + 1)
    assert int(got.count) == int(want.count)
    for g, w in zip(got.records, want.records):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    codes = np.asarray(got.records[0])
    assert codes.ndim == (1 if k <= KMER_ONE_WORD_K else 2)


@pytest.mark.parametrize("k,canonical", [(12, False), (21, True)])
def test_lowered_kmer_stats_has_no_gather(k, canonical):
    op = pull("kmer-stats", k=k, canonical=canonical)
    records = {"data": jax.ShapeDtypeStruct((CAP, WIDTH), jnp.uint8),
               "len": jax.ShapeDtypeStruct((CAP,), jnp.int32)}
    count = jax.ShapeDtypeStruct((), jnp.int32)
    text = jax.jit(lambda r, c: op(make_partition(r, c))).lower(
        records, count).as_text()
    assert "stablehlo.gather" not in text
    assert text.count("stablehlo.sort") == 1
    assert "is_stable = false" in text
