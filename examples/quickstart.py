"""Quickstart: the paper's Listing 1 (GC count), line for line — now fed
from an on-disk FASTA file through the repro.io ingestion subsystem.

  PYTHONPATH=src:. python examples/quickstart.py

A genome is written as FASTA, ingested via a pluggable storage backend
(LocalFS here; swap in ``backend="s3"`` for the emulated remote tier), and
the POSIX pipeline of Listing 1 runs over byte records:
  grep -o '[GC]' /dna | wc -l   ->  grep-chars GC
  awk '{s+=$1} END {print s}'   ->  awk-sum
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import MaRe, PlanTypeError, TextFile, DEFAULT_CACHE
from repro.io import fasta_source


def write_genome(path: str, n_bases: int = 100_000, seed: int = 42) -> str:
    """Write a random ATGC genome as FASTA; return the sequence string."""
    rng = np.random.default_rng(seed)
    seq = "".join(np.array(list("ATGC"))[rng.integers(0, 4, size=n_bases)])
    with open(path, "w") as f:
        f.write(">chr1 quickstart genome\n")
        for i in range(0, len(seq), 70):
            f.write(seq[i:i + 70] + "\n")
    return seq


def main():
    enable_compile_cache()
    tmp = tempfile.mkdtemp(prefix="mare_quickstart_")
    fasta = os.path.join(tmp, "genome.fa")
    seq = write_genome(fasta)

    gc_count = (
        MaRe.from_source(fasta_source(fasta, split_bytes=1 << 14)).map(
            input_mount=TextFile("/dna"),
            output_mount=TextFile("/count"),
            image="ubuntu",
            command="grep-chars GC",
        ).reduce(
            input_mount=TextFile("/counts"),
            output_mount=TextFile("/sum"),
            image="ubuntu",
            command="awk-sum",
        ))

    # The chain above is lazy: nothing has executed yet.  describe() shows
    # the pending stage DAG that the planner will fuse into ONE program.
    print(gc_count.describe())

    (total,) = gc_count.collect(shard=0)
    expected = seq.count("G") + seq.count("C")
    print(f"GC count: {int(total[0])} (expected {expected})")
    assert int(total[0]) == expected

    # Interactive re-execution (paper Fig. 6): building the same pipeline
    # again hits the compile cache — zero re-trace, zero re-compile.
    before = DEFAULT_CACHE.stats()
    rerun = (
        MaRe.from_source(fasta_source(fasta, split_bytes=1 << 14)).map(
            input_mount=TextFile("/dna"),
            output_mount=TextFile("/count"),
            image="ubuntu",
            command="grep-chars GC",
        ).reduce(
            input_mount=TextFile("/counts"),
            output_mount=TextFile("/sum"),
            image="ubuntu",
            command="awk-sum",
        ))
    (total2,) = rerun.collect(shard=0)
    after = DEFAULT_CACHE.stats()
    assert int(total2[0]) == expected
    assert after["misses"] == before["misses"], "re-run must not recompile"
    print(f"re-run hit the compile cache: {after}")

    # Typed image manifests: a mistyped pipeline fails while BUILDING the
    # chain — grep-count emits (i32) count records, grep-chars requires
    # byte records — instead of a shape error from inside the fused trace.
    try:
        (MaRe((np.arange(64, dtype=np.int32) % 4,))
         .map(image="ubuntu", command="grep-count 2 3")
         .map(image="ubuntu", command="grep-chars GC"))
        raise AssertionError("mistyped chain must not build")
    except PlanTypeError as e:
        print(f"plan-time type check: {e}")
    print("OK")


if __name__ == "__main__":
    main()
