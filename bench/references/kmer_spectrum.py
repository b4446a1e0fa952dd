"""The k-mer spectrum of a read set, as GenomeScope takes it from
``jellyfish count -C -m <k>`` then ``jellyfish histo``: for each ``b`` in
``1 .. high - 1``, how many distinct k-mers occur ``b`` times, and in bin
``high`` how many occur ``high`` times or more. A k-mer is counted over
the windows of each read that hold no ``N``; ``canonical`` counts a k-mer
and its reverse complement as one (the lesser of the two 2-bit codes,
A=0 C=1 G=2 T=3, first base most significant).

Parameters: ``k`` (at most 31), ``canonical``, ``high``. The plain NumPy
count imports nothing of the program: each block of reads gives its
windows' ``uint64`` codes, the codes go to 256 buckets by their low
bits, and each bucket's distinct codes are counted by ``np.unique``, in
threads.

The number compared, ``wrong_spectrum_bins``, is the most wrong bins of
any one sampled job: bins whose sum or count differs from the reference,
plus bins returned twice or out of range, plus 1 where ``sum of b x
sums[b]`` differs from the valid windows (each k-mer counted at most
``high`` times, as its bin does). The limit is 0: the configuration
states exact counts.

The control counts forward codes where the job asks for canonical ones:
the guarantee it breaks is that a k-mer and its reverse complement are
one k-mer.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

NUMBER = "wrong_spectrum_bins"
LIMIT = 0

#: Reads per block of the count (bounds its host memory).
_ROWS = 1 << 16
_BUCKETS = 256


def _codes(seq: np.ndarray) -> np.ndarray:
    lut = np.zeros(256, np.uint64)
    lut[[ord("C"), ord("G"), ord("T")]] = [1, 2, 3]
    return lut[seq]


def _windows(code: np.ndarray, k: int) -> np.ndarray:
    """``uint64 [rows, L - k + 1]`` packed k-mers of the 2-bit ``code``,
    built by doubling the window length (log k passes, not k)."""
    acc, width = code, 1
    parts = {1: code}
    while 2 * width <= k:
        acc = (acc[:, :-width] << np.uint64(2 * width)) | acc[:, width:]
        width *= 2
        parts[width] = acc
    out, done = None, 0
    for w in sorted(parts, reverse=True):
        if done + w <= k:
            p = parts[w][:, done:]
            out = p if out is None else \
                (out[:, :p.shape[1]] << np.uint64(2 * w)) | p[:, :out.shape[1]]
            done += w
    return out[:, :code.shape[1] - k + 1]


def kmer_codes(seq: np.ndarray, k: int, canonical: bool) -> np.ndarray:
    """``uint64`` codes of the windows of ``seq`` (``[n, L]`` bytes) that
    hold no ``N``, row by row."""
    code = _codes(seq)
    fwd = _windows(code, k)
    if canonical:
        rc = _windows(np.uint64(3) - code[:, ::-1], k)[:, ::-1]
        fwd = np.minimum(fwd, rc)
    n_seen = np.concatenate(
        [np.zeros((seq.shape[0], 1), np.int32),
         np.cumsum(seq == ord("N"), axis=1, dtype=np.int32)], axis=1)
    ok = n_seen[:, k:] == n_seen[:, :-k]
    return fwd[ok]


def kmer_counts(seq: np.ndarray, k: int, canonical: bool) -> np.ndarray:
    """``int64`` occurrences of each distinct k-mer, in no set order."""
    blocks = list(range(0, seq.shape[0], _ROWS))
    workers = max(1, min(8, os.cpu_count() or 1))

    def bucket(lo: int) -> List[np.ndarray]:
        codes = kmer_codes(seq[lo:lo + _ROWS], k, canonical)
        which = (codes & np.uint64(_BUCKETS - 1)).astype(np.uint8)
        order = np.argsort(which, kind="stable")
        edges = np.concatenate(
            [[0], np.cumsum(np.bincount(which, minlength=_BUCKETS))])
        codes = codes[order]
        return [codes[edges[b]:edges[b + 1]] for b in range(_BUCKETS)]

    with ThreadPoolExecutor(max_workers=workers) as ex:
        parts = list(ex.map(bucket, blocks))

        def count(b: int) -> np.ndarray:
            codes = np.concatenate([p[b] for p in parts])
            return np.unique(codes, return_counts=True)[1].astype(np.int64)

        return np.concatenate(list(ex.map(count, range(_BUCKETS))))


def spectrum(seq: np.ndarray, k: int, canonical: bool, high: int
             ) -> Tuple[np.ndarray, int]:
    """``(int64 [high + 1] spectrum, valid windows)``, each k-mer's
    windows counted at most ``high`` times."""
    clipped = np.minimum(kmer_counts(seq, k, canonical), high)
    return np.bincount(clipped, minlength=high + 1), int(clipped.sum())


def _params(s: Dict[str, Any]) -> Tuple[int, bool, int]:
    return int(s["k"]), bool(s.get("canonical", False)), int(s["high"])


def spectrum_errors(keys, sums, counts, want: Tuple[np.ndarray, int]
                    ) -> int:
    """Bins whose sum or count differs, bins returned twice or out of
    range, and 1 where the windows the answer accounts for differ."""
    spec, windows = want
    keys = np.asarray(keys).astype(np.int64)
    inside = (keys >= 0) & (keys < spec.size)
    kept = keys[inside]
    seen = np.bincount(kept, minlength=spec.size)
    bad = int(np.count_nonzero(~inside)) + int(np.sum(seen[seen > 1] - 1))

    def dense(vals) -> np.ndarray:
        got = np.zeros_like(spec)
        got[kept] = np.asarray(vals)[inside]
        return got

    got_sums, got_counts = dense(sums), dense(counts)
    bad += int(np.count_nonzero((got_sums != spec) | (got_counts != spec)))
    accounted = int(np.arange(spec.size, dtype=np.int64) @ got_sums)
    return bad + int(accounted != windows)


def answer(out: Any) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The collected ``(bins, (sums,), counts)`` as host arrays."""
    keys, (sums,), counts = out
    return np.asarray(keys), np.asarray(sums), np.asarray(counts)


def expected(data, specs: List[Dict[str, Any]]) -> List[Tuple]:
    return [spectrum(data.seq, *_params(s)) for s in specs]


def control(data, specs: List[Dict[str, Any]]) -> List[Tuple]:
    """The control's answers, in the form :func:`answer` gives."""
    out = []
    for s in specs:
        k, _, high = _params(s)
        spec, _ = spectrum(data.seq, k, False, high)
        keys = np.flatnonzero(spec)
        out.append((keys, spec[keys], spec[keys]))
    return out


def number(pairs: Sequence[Tuple[Tuple, Tuple]]) -> int:
    return max((spectrum_errors(*got, want) for got, want in pairs),
               default=0)
