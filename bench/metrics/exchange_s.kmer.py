"""Device seconds per job under ``*/exchange``: the keyed stage's hash
bucketing, all-to-all and exchange counters (``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.scope_seconds(run, lambda s: s.endswith("/exchange"))
