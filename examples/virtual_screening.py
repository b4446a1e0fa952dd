"""Paper Listing 2: Virtual Screening — map (docking) + reduce (top-30).

  PYTHONPATH=src:. python examples/virtual_screening.py

The FRED docking stage is a surrogate scorer ContainerOp; the sdsorter
top-k combiner is the `toolbox/topk` image (Pallas topk_reduce kernel on
TPU).  Results are validated against the single-core oracle, mirroring the
paper's own 1K-molecule correctness check.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.apps import make_library, virtual_screening, vs_reference
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    library = make_library(20_000, seed=7)
    scores, mol_ids = virtual_screening(library, top=30)
    ref_scores, ref_ids = vs_reference(library, top=30)
    print("top-5 poses (score, molecule):")
    order = np.argsort(-np.asarray(scores))
    for i in order[:5]:
        print(f"  {float(scores[i]):8.3f}  mol {int(mol_ids[i])}")
    assert set(np.asarray(mol_ids).tolist()) == set(ref_ids.tolist()), \
        "parallel top-30 differs from single-core oracle"
    print("OK: matches single-core FRED+sdsorter oracle")


if __name__ == "__main__":
    main()
