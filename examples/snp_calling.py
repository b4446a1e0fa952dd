"""Paper Listing 3: SNP calling — map (align) + repartitionBy (chromosome)
+ map (call) + reduce (concat).

  PYTHONPATH=src:. python examples/snp_calling.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.apps import make_library, snp_calling
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    reads = make_library(8_192, seed=3)
    chrom, score, read_id = snp_calling(reads)
    n = len(np.asarray(read_id))
    print(f"called {n} variants across "
          f"{len(set(np.asarray(chrom).tolist()))} chromosomes")
    by_chrom = {}
    for c in np.asarray(chrom).tolist():
        by_chrom[c] = by_chrom.get(c, 0) + 1
    top = sorted(by_chrom.items(), key=lambda kv: -kv[1])[:5]
    for c, k in top:
        print(f"  chr{c:<3} {k} variants")
    assert n > 0
    print("OK")


if __name__ == "__main__":
    main()
