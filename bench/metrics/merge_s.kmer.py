"""Device seconds per job under ``*/merge``: the keyed stage's segment
reduce of what the exchange delivered (``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.scope_seconds(run, lambda s: s.endswith("/merge"))
