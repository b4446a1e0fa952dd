"""Device seconds per job under the sorted keyed stage's scope (its
``combine``; on a mesh also ``exchange`` and ``merge``): the sort on the
two-word key, the segmented fold and the compaction."""
from bench import sortagg


def read(run):
    return sortagg.stage_seconds(run, lambda d, kind: d == 0)
