"""The benchmark of MaRe on the TPU: see ``bench/run.py`` and PERF.md."""
