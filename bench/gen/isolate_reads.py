"""Short reads sequenced from one bacterial isolate: a seeded random genome,
reads taken from it at uniform positions and from either strand, with
substitution errors and ``N`` bases.

Configuration keys: ``genome_len``, ``read_len``, ``reads_per_chip``,
``error_rate``, ``revcomp_share``, ``n_bits``. All of it comes from
``--seed``, drawn in this order:

1. the genome: ``genome_len`` bases drawn uniformly from A/C/G/T
   (``default_rng([seed, 0])``), so it has no repeats and no GC bias;
2. per block of :data:`_BLOCK` reads (``default_rng([seed, 1, i])``, so
   blocks fill in parallel threads): each read's start, uniform over
   the genome's ``genome_len - read_len + 1`` positions;
3. its strand: the reverse complement with chance ``revcomp_share``;
4. one 32-bit draw a base: its low ``32 - n_bits`` bits below
   ``error_rate`` of their range make a substitution error (the base
   becomes one of the other three, uniformly), and its top ``n_bits``
   bits all zero make an ``N`` (chance ``2**-n_bits``).

At ``reads_per_chip`` 1,048,576 reads of 150 bp over 4,641,652 bp the
coverage is 33.9x: each genomic k-mer occurs about 26 times at k = 21,
and errors add about 16 million k-mers seen once.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

from bench.data import Reads, empty_reads, write_headers

#: Reads are made in blocks of this many, so the per-base draws stay
#: small beside the FASTA buffer itself.
_BLOCK = 1 << 17

_ACGT = np.frombuffer(b"ACGT", np.uint8)


def make_genome(length: int, seed: int) -> np.ndarray:
    """``uint8 [length]`` 2-bit base codes (A=0 C=1 G=2 T=3)."""
    return np.random.default_rng([seed, 0]).integers(
        0, 4, length, dtype=np.uint8)


def make_reads(n: int, read_len: int, genome_len: int, error_rate: float,
               revcomp_share: float, n_bits: int, seed: int) -> Reads:
    if genome_len < read_len:
        raise ValueError(f"genome_len {genome_len} is shorter than a read "
                         f"({read_len})")
    if not 0 < n_bits < 32:
        raise ValueError(f"n_bits must lie in 1..31, got {n_bits}")
    genome = make_genome(genome_len, seed)
    reads = empty_reads(n, read_len)
    err_bits = 32 - n_bits
    err_below = np.uint32(round(error_rate * (1 << err_bits)))
    err_mask = np.uint32((1 << err_bits) - 1)

    def fill(i: int) -> None:
        lo, hi = i * _BLOCK, min(n, (i + 1) * _BLOCK)
        m = hi - lo
        rng = np.random.default_rng([seed, 1, i])
        starts = rng.integers(0, genome_len - read_len + 1, m)
        codes = genome[starts[:, None] + np.arange(read_len)[None, :]]
        minus = rng.random(m) < revcomp_share
        codes[minus] = 3 - codes[minus, ::-1]
        words = m * read_len
        draw = rng.bit_generator.random_raw((words + 1) // 2).view(
            np.uint32)[:words].reshape(m, read_len)
        err = (draw & err_mask) < err_below
        shift = rng.integers(1, 4, int(err.sum()), dtype=np.uint8)
        codes[err] = (codes[err] + shift) % 4
        bases = _ACGT[codes]
        bases[(draw >> np.uint32(err_bits)) == 0] = ord("N")
        write_headers(reads, lo, hi)
        reads.seq[lo:hi] = bases

    blocks = range((n + _BLOCK - 1) // _BLOCK)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, blocks))
    return reads


def make(cfg: Dict[str, Any], chips: int, seed: int) -> Reads:
    return make_reads(int(cfg["reads_per_chip"]) * chips,
                      int(cfg["read_len"]), int(cfg["genome_len"]),
                      float(cfg["error_rate"]), float(cfg["revcomp_share"]),
                      int(cfg["n_bits"]), seed)
