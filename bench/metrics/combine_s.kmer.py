"""Device seconds per job under ``*/combine``: the keyed stage's key
selection and map-side segment reduce into the 4**12 table, with its
compaction (``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.scope_seconds(run, lambda s: s.endswith("/combine"))
