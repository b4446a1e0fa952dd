"""Reads whose bases are drawn independently and uniformly from A/C/G/T,
each an ``N`` with chance ``2**-n_bits``.

Configuration keys: ``reads_per_chip``, ``read_len``, ``n_bits``. The
cell makes ``reads_per_chip`` reads for each of its chips, drawn from
``--seed`` in bulk with NumPy (the base mix of ``chip_smoke.py``'s
generator). Every k-mer is as likely as any other: the key distribution
is flat, not the skewed one of reads sequenced from a genome.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

from bench.data import Reads, empty_reads, write_headers

#: Reads are generated in blocks of this many, so the transient draw
#: arrays stay small beside the FASTA buffer itself.
_BLOCK = 1 << 20


def _base_table(n_bits: int) -> np.ndarray:
    """``uint8 [65536]``: a 16-bit draw to a base. The low 2 bits pick
    A/C/G/T; the next ``n_bits`` all zero make an ``N``."""
    if not 0 < n_bits <= 14:
        raise ValueError(f"n_bits must lie in 1..14, got {n_bits}")
    draw = np.arange(1 << 16)
    table = np.frombuffer(b"ACGT", np.uint8)[draw & 3].copy()
    table[(draw >> 2) & ((1 << n_bits) - 1) == 0] = ord("N")
    return table


def make_reads(n: int, read_len: int, n_bits: int, seed: int) -> Reads:
    """``n`` reads from ``seed``; a base is ``N`` with chance 2**-n_bits.

    Block ``i`` of :data:`_BLOCK` reads is drawn from its own generator
    ``default_rng([seed, i])``, so blocks fill in parallel threads and the
    reads depend on the seed alone.
    """
    reads = empty_reads(n, read_len)
    table = _base_table(n_bits)

    def fill(i: int) -> None:
        lo, hi = i * _BLOCK, min(n, (i + 1) * _BLOCK)
        write_headers(reads, lo, hi)
        words = (hi - lo) * read_len
        raw = np.random.default_rng([seed, i]).bit_generator.random_raw(
            (words + 3) // 4)
        draw = raw.view(np.uint16)[:words].reshape(hi - lo, read_len)
        reads.seq[lo:hi] = table[draw]

    blocks = range((n + _BLOCK - 1) // _BLOCK)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, blocks))
    return reads


def make(cfg: Dict[str, Any], chips: int, seed: int) -> Reads:
    return make_reads(int(cfg["reads_per_chip"]) * chips,
                      int(cfg["read_len"]), int(cfg["n_bits"]), seed)
