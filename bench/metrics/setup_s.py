"""Process start to window start, by the host clock: data generation,
ingest, compilation (or compile-cache loads) and the warm-up actions."""


def read(run):
    return run.setup_s
