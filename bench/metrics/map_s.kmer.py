"""Device seconds per job under the map stages' scopes (``s<i>.map``):
the ``kmer-stats`` windows (``bench.scopes``)."""
import re

from bench import scopes


def read(run):
    return scopes.scope_seconds(
        run, lambda s: re.fullmatch(r"s\d+\.map", s) is not None)
