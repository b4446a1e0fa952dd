"""A keyed stage on a one-device mesh is lowered without the exchange to
itself (and, with the combiner on, without the merge after it): the same
records, counters and errors as the exchanging lowering, which a 4-device
mesh still takes (its results come from ``tests/distributed/local_keyed.py``
in a child process, as the main process stays 1-device)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import compat
from repro.core import MaRe, PlanCache, from_host

HERE = os.path.dirname(__file__)
CASES = ["-".join((mode, op, dtype))
         for mode in ("combiner", "nocombiner", "salt8")
         for op in ("sum", "max", "min") for dtype in ("int32", "float32")]
MODES = {"combiner": {}, "nocombiner": {"combiner": False},
         "salt8": {"combiner": False, "salt": 8}}
NUM_KEYS = 48


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    """The 4-device results of every case, and the facts of its
    combiner-on program, from one child process."""
    out = tmp_path_factory.mktemp("local_keyed")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(
        os.path.join(HERE, "..", "src")))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "distributed", "local_keyed.py"),
         str(out)], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-4000:]
    return out


def _key_first(recs):
    return recs[0]


def _value_second(recs):
    return (recs[1],)


def _one_device(data, **kw):
    mesh = compat.make_mesh((1,), ("data",))
    return MaRe(from_host(data, mesh), plan_cache=PlanCache()).reduce_by_key(
        _key_first, value_by=_value_second, **kw)


def _groupby(keys, vals, op):
    fold = {"sum": np.sum, "max": np.max, "min": np.min}[op]
    uniq = np.unique(keys)
    return (uniq.astype(np.int32),
            np.array([fold(vals[keys == k]) for k in uniq], vals.dtype),
            np.array([(keys == k).sum() for k in uniq], np.int32))


@pytest.mark.parametrize("case", CASES)
def test_one_device_keyed_stage_matches_groupby_and_mesh(case, four_devices):
    mode, op, _ = case.split("-")
    got4 = np.load(four_devices / f"{case}.npz")
    keys, vals = got4["keys"], got4["vals"]
    q = _one_device((keys, vals), op=op, num_keys=NUM_KEYS, **MODES[mode])
    out_keys, (out_vals,), out_counts = q.collect()
    assert q.report().diagnostics["stage0.local_keyed"] == 1
    got = (out_keys, out_vals, out_counts)
    for a, b in zip(got, _groupby(keys, vals, op)):
        assert a.dtype == b.dtype and np.array_equal(a, b), case
    order = np.argsort(got4["out_keys"], kind="stable")
    mesh4 = tuple(got4[n][order]
                  for n in ("out_keys", "out_vals", "out_counts"))
    for a, b in zip(got, mesh4):
        assert a.dtype == b.dtype and np.array_equal(a, b), case


def test_one_device_keyed_stage_has_no_exchange_or_merge(four_devices):
    keys = np.arange(256, dtype=np.int32) % 40
    q = _one_device((keys, keys), op="sum", num_keys=NUM_KEYS)
    q.collect()
    (prog,) = q.plan_cache.programs()
    scopes = set(prog.op_scopes().values())
    assert "s0.reduce_by_key/combine" in scopes
    assert not [s for s in scopes if s.endswith(("/exchange", "/merge"))]
    assert prog.local_keyed == {0: 1}
    assert q.report().diagnostics["stage0.local_keyed"] == 1
    facts = json.loads((four_devices / "facts.json").read_text())
    assert {"s0.reduce_by_key/exchange",
            "s0.reduce_by_key/merge"} <= set(facts["scopes"])
    assert facts["local_keyed"] == 0


def _counter_data():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 37, 1000).astype(np.int32)
    keys[:37] = np.arange(37)                   # 37 distinct keys
    return keys, rng.integers(0, 10, 1000).astype(np.int32)


@pytest.mark.parametrize("mode,exchanged,max_send,buffer_rows", [
    ("combiner", 37, 37, 64),                 # one record a key, 64-row table
    ("nocombiner", 1000, 1000, 1000),         # every record, input capacity
    ("salt8", 1037, 1000, 1064),              # both hops: records, then keys
])
def test_one_device_exchange_counters(mode, exchanged, max_send,
                                      buffer_rows):
    q = _one_device(_counter_data(), op="sum", num_keys=64, **MODES[mode])
    q.collect()
    d = q.report().diagnostics
    assert (d["stage0.exchanged_records"], d["stage0.max_send_count"],
            d["stage0.exchange_buffer_rows"]) == (exchanged, max_send,
                                                  buffer_rows)
    assert d["stage0.shuffle_dropped"] == d["stage0.key_overflow"] == 0


@pytest.mark.parametrize("mode,capacity,dropped", [
    ("combiner", 10, 27), ("nocombiner", 600, 400), ("salt8", 600, 400)])
def test_one_device_too_small_capacity_still_raises(mode, capacity, dropped):
    q = _one_device(_counter_data(), op="sum", num_keys=64,
                    capacity=capacity, **MODES[mode])
    with pytest.raises(RuntimeError,
                       match=f"overflow: {dropped} records dropped"):
        q.collect()
