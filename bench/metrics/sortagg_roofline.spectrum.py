"""Share of the HBM roofline (``bench/peaks.json``) that the sorted keyed
stage reached, in percent: the least bytes any implementation of the
stage moves, ``bench.sortagg.sortagg_bytes`` of a job's valid windows and
distinct keys, over the peak bandwidth, over its device seconds per job
(``sortagg_s.spectrum``)."""
from bench import sortagg
from bench.peaks import peaks


def read(run):
    seconds = sortagg.stage_seconds(run, lambda d, kind: d == 0)
    windows = sortagg.spectrum_windows(run)
    distinct = sortagg.distinct_keys(run)
    if not seconds or windows is None or distinct is None:
        return None
    import jax
    bandwidth = peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * sortagg.sortagg_bytes(windows, distinct) / bandwidth \
        / seconds
