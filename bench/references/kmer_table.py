"""Occurrences of every 2-bit packed k-mer (A=0 C=1 G=2 T=3) over the
windows of each read that hold no ``N``: the whole table that a
``map(kmer-stats)`` then ``reduce_by_key(sum)`` job collects.

Parameters: ``k``. The plain NumPy count (the k-mer reference of
``chip_smoke.py``, copied and computed in blocks of reads) imports
nothing of the program. The number compared, ``wrong_table_entries``, is
the most wrong entries in any one sampled job's table: keys whose sum or
count differs, plus keys returned twice or out of range. The limit is 0:
the configuration states exact counts.

The control counts the windows that hold an ``N`` too, the ``N`` read as
``A``: the skip of ``N`` windows is the guarantee it breaks.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

NUMBER = "wrong_table_entries"
LIMIT = 0

#: Reads per block of the count (bounds its host memory).
_ROWS = 1 << 17


def _codes(seq: np.ndarray) -> np.ndarray:
    lut = np.zeros(256, np.uint32)
    lut[[ord("C"), ord("G"), ord("T")]] = [1, 2, 3]
    return lut[seq]


def _windows(code: np.ndarray, k: int) -> np.ndarray:
    """``uint32 [rows, L - k + 1]`` packed k-mers of the 2-bit ``code``,
    built by doubling the window length (log k passes, not k)."""
    acc, width = code, 1
    parts = {1: code}
    while 2 * width <= k:
        acc = (acc[:, :-width] << (2 * width)) | acc[:, width:]
        width *= 2
        parts[width] = acc
    out, done = None, 0
    for w in sorted(parts, reverse=True):
        if done + w <= k:
            p = parts[w][:, done:]
            out = p if out is None else \
                (out[:, :p.shape[1]] << (2 * w)) | p[:, :out.shape[1]]
            done += w
    return out[:, :code.shape[1] - k + 1]


def kmer_table(seq: np.ndarray, k: int, skip_n: bool = True) -> np.ndarray:
    """``int64 [4**k]`` counts of k-mers over ``seq`` (``[n, L]`` bytes).

    ``skip_n=False`` is the control: windows with an ``N`` count too, the
    ``N`` read as ``A``. Threads count interleaved blocks of reads, each
    into a table of its own.
    """
    nw = seq.shape[1] - k + 1
    blocks = range(0, seq.shape[0], _ROWS)
    workers = max(1, min(8, os.cpu_count() or 1, len(blocks)))

    def count(first: int) -> np.ndarray:
        out = np.zeros(4 ** k, np.int64)
        for lo in blocks[first::workers]:
            block = seq[lo:lo + _ROWS]
            acc = _windows(_codes(block), k).reshape(-1)
            out += np.bincount(acc, minlength=4 ** k)
            if skip_n:
                # take back every window that holds an N (N is rare)
                rows, cols = np.nonzero(block == ord("N"))
                starts = cols[:, None] - np.arange(k)[None, :]
                ok = (starts >= 0) & (starts < nw)
                bad = np.unique((rows[:, None] * nw + starts)[ok])
                out -= np.bincount(acc[bad], minlength=4 ** k)
        return out

    with ThreadPoolExecutor(max_workers=workers) as ex:
        return sum(ex.map(count, range(workers)))


def table_errors(keys, sums, counts, expected: np.ndarray) -> int:
    """Keys whose sum or count differs from ``expected``, plus every key
    returned twice or out of range: 0 for an exact table."""
    keys = np.asarray(keys).astype(np.int64)
    inside = (keys >= 0) & (keys < expected.size)
    kept = keys[inside]
    seen = np.bincount(kept, minlength=expected.size)
    bad = int(np.count_nonzero(~inside)) + int(np.sum(seen[seen > 1] - 1))
    for vals in (sums, counts):
        got = np.zeros_like(expected)
        got[kept] = np.asarray(vals)[inside]
        bad += int(np.count_nonzero(got != expected))
    return bad


def answer(out: Any) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The collected ``(keys, (sums,), counts)`` as host arrays."""
    keys, (sums,), counts = out
    return np.asarray(keys), np.asarray(sums), np.asarray(counts)


def expected(data, specs: List[Dict[str, Any]]) -> List[np.ndarray]:
    return [kmer_table(data.seq, int(s["k"])) for s in specs]


def control(data, specs: List[Dict[str, Any]]) -> List[Tuple]:
    """The control's answers, in the form :func:`answer` gives."""
    out = []
    for s in specs:
        table = kmer_table(data.seq, int(s["k"]), skip_n=False)
        keys = np.flatnonzero(table)
        out.append((keys, table[keys], table[keys]))
    return out


def number(pairs: Sequence[Tuple[Tuple, np.ndarray]]) -> int:
    return max((table_errors(*got, want) for got, want in pairs),
               default=0)
