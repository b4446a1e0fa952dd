"""Device seconds per job under the map stages before the sorted keyed
stage (``s0.map``: the k = 21 canonical windows and their compaction)."""
from bench import sortagg


def read(run):
    return sortagg.stage_seconds(run, lambda d, kind: d < 0
                                 and kind == "map")
