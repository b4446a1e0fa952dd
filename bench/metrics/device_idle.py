"""Share of the traced window in which the chip ran no operation
(``bench.trace``), averaged over the cell's chips, in percent. Read for
``device_idle.<part>``, one metric for each end-to-end metric it moves."""


def read(run):
    return run.trace.idle_pct if run.trace is not None else None
