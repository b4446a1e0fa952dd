"""Device seconds per job under the stages after the sorted keyed stage:
the ``kmer-histo`` map and the spectrum's dense keyed stage."""
from bench import sortagg


def read(run):
    return sortagg.stage_seconds(run, lambda d, kind: d > 0)
