"""Short reads held in host memory as a FASTA file, and the store that
serves them.

A generator of reads (``bench/gen/<name>.py``) fills a :class:`Reads`:
one header and one sequence line per read (the layout of
``chip_smoke.py``'s FASTA, kept here so that the yardstick does not move
when that script changes). :class:`MemoryStore` serves the bytes to
``repro.io`` through its ``StorageBackend`` contract, so ingest runs its
real split, fetch, frame and pack path and nothing is written to disk.
"""
from __future__ import annotations

import numpy as np

from repro.io import DataSource, FastaFormat, StorageBackend


class Reads:
    """A FASTA buffer of ``n`` reads of ``read_len`` bases.

    ``lines`` is ``[n, row]`` uint8: ``>r<digits>\\n``, the sequence and a
    newline.  ``seq`` is the ``[n, read_len]`` view of the bases.
    """

    def __init__(self, lines: np.ndarray, header: int, read_len: int):
        self.lines = lines
        self.header = header
        self.read_len = read_len

    @property
    def n(self) -> int:
        return self.lines.shape[0]

    @property
    def row_bytes(self) -> int:
        return self.lines.shape[1]

    @property
    def seq(self) -> np.ndarray:
        return self.lines[:, self.header:self.header + self.read_len]

    @property
    def bases(self) -> int:
        return self.n * self.read_len

    def source(self, name: str, split_bytes: int, rotate: int = 0
               ) -> DataSource:
        """The reads as the FASTA file ``<name>.fa``, rotated by
        ``rotate`` whole reads (:class:`MemoryStore`)."""
        return DataSource(MemoryStore(self, f"{name}.fa", rotate=rotate),
                          FastaFormat(), split_bytes=split_bytes)


def empty_reads(n: int, read_len: int) -> Reads:
    """``n`` reads, their bytes not yet written: a generator fills each
    block with :func:`write_headers` and ``reads.seq``."""
    digits = max(7, len(str(max(n - 1, 0))))
    header = digits + 3                                # ">r" digits "\n"
    return Reads(np.empty((n, header + read_len + 1), np.uint8), header,
                 read_len)


def write_headers(reads: Reads, lo: int, hi: int) -> None:
    """Write ``>r<index>``, the newline after it and the newline after the
    sequence of reads ``lo`` to ``hi``."""
    rows = reads.lines[lo:hi]
    digits = reads.header - 3
    rows[:, :2] = np.frombuffer(b">r", np.uint8)
    idx = np.arange(lo, hi)
    for d in range(digits):
        rows[:, 2 + d] = 48 + (idx // 10 ** (digits - 1 - d)) % 10
    rows[:, reads.header - 1] = ord("\n")
    rows[:, -1] = ord("\n")


class MemoryStore(StorageBackend):
    """One FASTA object held in memory, rotated by ``rotate`` whole reads.

    Each job of a batch cell reads its own rotation under its own path:
    the same multiset of reads, so the same answer, in a different byte
    order, so no job is a byte-for-byte repeat of an earlier one.
    """

    name = "memory"

    def __init__(self, reads: Reads, path: str, rotate: int = 0):
        self._buf = reads.lines.reshape(-1)
        self._path = path
        self._shift = (rotate % max(reads.n, 1)) * reads.row_bytes

    def list(self):
        return [self._path]

    def size(self, path: str) -> int:
        return self._buf.size

    def read_range(self, path: str, start: int, stop: int) -> bytes:
        size = self._buf.size
        start, stop = max(0, start), min(stop, size)
        if stop <= start:
            return b""
        a, b = (start + self._shift) % size, (stop + self._shift) % size
        if a < b or b == 0:
            return self._buf[a:b or size].tobytes()
        return self._buf[a:].tobytes() + self._buf[:b].tobytes()
