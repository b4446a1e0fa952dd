"""Seconds per job in which chip 0 ran no op and the window's thread had
no program span open (``bench.scopes``): idle that the program's spans
cannot explain."""
from bench import scopes


def read(run):
    return scopes.idle_seconds(run, scopes.UNSPANNED)
