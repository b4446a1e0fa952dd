"""Segment-reduce kernel (bounded key table) vs jnp oracle vs numpy."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import segment_reduce, segment_reduce_ref
from repro.kernels.segment_reduce import monoid_identity, resolve_use_kernel

RNG = np.random.default_rng(2)


def _case(n, num_keys, d, dtype, spill=True):
    lo = -3 if spill else 0
    hi = num_keys + (5 if spill else 0)
    keys = RNG.integers(lo, hi, size=n).astype(np.int32)
    if np.issubdtype(dtype, np.floating):
        vals = RNG.normal(size=(n, d) if d else (n,)).astype(dtype)
    else:
        vals = RNG.integers(0, 100, size=(n, d) if d else (n,)).astype(dtype)
    valid = RNG.random(n) < 0.8
    return keys, vals, valid


def _np_segment_sum(keys, vals, valid, num_keys):
    ok = valid & (keys >= 0) & (keys < num_keys)
    tab = np.zeros((num_keys,) + vals.shape[1:], vals.dtype)
    np.add.at(tab, keys[ok], vals[ok])
    cnt = np.bincount(keys[ok], minlength=num_keys)
    ovf = int(np.sum(valid & ~((keys >= 0) & (keys < num_keys))))
    return tab, cnt, ovf


@pytest.mark.parametrize("n,num_keys,d,block", [
    (1000, 37, 3, 128), (256, 128, 0, 64), (64, 8, 1, 8), (513, 200, 2, 256),
])
def test_segment_sum_kernel_vs_numpy(n, num_keys, d, block):
    keys, vals, valid = _case(n, num_keys, d, np.float32)
    got = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), num_keys,
                         op="sum", valid=jnp.asarray(valid),
                         use_kernel=True, block=block, interpret=True)
    tab, cnt, ovf = _np_segment_sum(keys, vals, valid, num_keys)
    np.testing.assert_allclose(np.asarray(got.values[0]), tab,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.counts), cnt)
    assert int(got.overflow) == ovf


def test_segment_sum_kernel_matches_ref_int32():
    keys, vals, valid = _case(500, 64, 2, np.int32)
    ker = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), 64,
                         op="sum", valid=jnp.asarray(valid),
                         use_kernel=True, block=128, interpret=True)
    ref = segment_reduce_ref(jnp.asarray(keys), (jnp.asarray(vals),), 64,
                             op="sum", valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(ker.values[0]),
                                  np.asarray(ref.values[0]))
    np.testing.assert_array_equal(np.asarray(ker.counts),
                                  np.asarray(ref.counts))
    assert int(ker.overflow) == int(ref.overflow)


@pytest.mark.parametrize("dtype", [np.int32, np.uint8, np.float32])
def test_tiled_matches_scatter_bit_for_bit(dtype):
    """Full-range int32 sums wrap exactly as the scatter oracle's do (the
    kernel sums bytes on the MXU and recombines them); narrow ints wrap
    in their own width; a NaN in a masked float slot never leaks."""
    n, num_keys = 3000, 300
    keys = RNG.integers(-2, num_keys + 2, n).astype(np.int32)
    valid = RNG.random(n) < 0.9
    if dtype == np.float32:
        vals = RNG.normal(size=(n, 2)).astype(dtype)
        vals[~valid] = np.nan
    else:
        info = np.iinfo(dtype)
        vals = RNG.integers(info.min, info.max, (n, 2),
                            dtype=np.int64).astype(dtype)
    args = (jnp.asarray(keys), (jnp.asarray(vals),), num_keys)
    ker = segment_reduce(*args, op="sum", valid=jnp.asarray(valid),
                         use_kernel=True, interpret=True)
    ref = segment_reduce_ref(*args, op="sum", valid=jnp.asarray(valid))
    if dtype == np.float32:
        np.testing.assert_allclose(np.asarray(ker.values[0]),
                                   np.asarray(ref.values[0]),
                                   rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(ker.values[0]),
                                      np.asarray(ref.values[0]))
    np.testing.assert_array_equal(np.asarray(ker.counts),
                                  np.asarray(ref.counts))
    assert int(ker.overflow) == int(ref.overflow)


@pytest.mark.parametrize("op", ["max", "min"])
def test_segment_minmax_ref_vs_numpy(op):
    keys, vals, valid = _case(400, 32, 0, np.float32)
    got = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), 32,
                         op=op, valid=jnp.asarray(valid))
    ok = valid & (keys >= 0) & (keys < 32)
    ident = float(monoid_identity(op, jnp.float32))
    exp = np.full(32, ident, np.float32)
    (np.maximum if op == "max" else np.minimum).at(exp, keys[ok], vals[ok])
    np.testing.assert_allclose(np.asarray(got.values[0]), exp, rtol=1e-6)


def test_segment_reduce_pytree_and_empty_values():
    keys = jnp.asarray(np.arange(16) % 4, jnp.int32)
    vals = {"a": jnp.ones((16,), jnp.float32),
            "b": jnp.ones((16, 2), jnp.int32)}
    got = segment_reduce(keys, vals, 4, op="sum", use_kernel=True)
    np.testing.assert_allclose(np.asarray(got.values["a"]), 4.0)
    np.testing.assert_array_equal(np.asarray(got.counts), [4, 4, 4, 4])
    empty = segment_reduce(keys, (), 4, op="sum", use_kernel=True)
    np.testing.assert_array_equal(np.asarray(empty.counts), [4, 4, 4, 4])
    assert int(empty.overflow) == 0


def test_segment_reduce_all_invalid():
    keys = jnp.asarray(np.zeros(32), jnp.int32)
    valid = jnp.zeros((32,), bool)
    for uk in (False, True):
        got = segment_reduce(keys, (jnp.ones((32,), jnp.float32),), 8,
                             op="sum", valid=valid, use_kernel=uk)
        assert np.asarray(got.counts).sum() == 0
        assert np.asarray(got.values[0]).sum() == 0
        assert int(got.overflow) == 0


def test_kernel_dispatch_policy():
    assert resolve_use_kernel(True, "sum") is True
    assert resolve_use_kernel(False, "sum") is False
    assert resolve_use_kernel(True, "max") is False   # kernel is sum-only
    assert resolve_use_kernel(None, "sum") in (True, False)


def test_unknown_monoid_raises():
    with pytest.raises(ValueError, match="unknown segment-reduce op"):
        segment_reduce_ref(jnp.zeros((4,), jnp.int32),
                           (jnp.zeros((4,), jnp.float32),), 2, op="mean")


# -- degenerate tilings & strategy engine (tiled kernel + autotuner) ----------

@pytest.mark.parametrize("n,num_keys,d,block,key_block", [
    (0, 8, 2, 64, 8),        # empty shard (short-circuits to scatter)
    (64, 1, 1, 16, 1),       # single key: one-row table
    (513, 200, 2, 128, 96),  # num_keys not divisible by key_block
    (200, 64, 3, 512, 16),   # block > n, many key tiles
])
def test_tiled_degenerate_tilings_match_numpy(n, num_keys, d, block,
                                              key_block):
    keys, vals, valid = _case(n, num_keys, d, np.float32)
    got = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), num_keys,
                         op="sum", valid=jnp.asarray(valid),
                         use_kernel=True, block=block, key_block=key_block,
                         interpret=True)
    tab, cnt, ovf = _np_segment_sum(keys, vals, valid, num_keys)
    np.testing.assert_allclose(np.asarray(got.values[0]), tab,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.counts), cnt)
    assert int(got.overflow) == ovf


def test_tiled_all_masked_records():
    keys = jnp.asarray(np.full(64, 5, np.int32))
    valid = jnp.zeros((64,), bool)
    got = segment_reduce(keys, (jnp.ones((64, 2), jnp.float32),), 32,
                         op="sum", valid=valid, use_kernel=True,
                         block=16, key_block=8, interpret=True)
    assert np.asarray(got.values[0]).sum() == 0
    assert np.asarray(got.counts).sum() == 0
    assert int(got.overflow) == 0


def test_tiled_hot_key_distribution():
    n, num_keys = 1024, 64
    keys = np.where(RNG.random(n) < 0.9, 7,
                    RNG.integers(0, num_keys, n)).astype(np.int32)
    vals = RNG.integers(0, 100, (n, 2)).astype(np.int32)
    valid = RNG.random(n) < 0.8
    got = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), num_keys,
                         op="sum", valid=jnp.asarray(valid),
                         use_kernel=True, block=128, key_block=16,
                         interpret=True)
    tab, cnt, ovf = _np_segment_sum(keys, vals, valid, num_keys)
    np.testing.assert_array_equal(np.asarray(got.values[0]), tab)
    np.testing.assert_array_equal(np.asarray(got.counts), cnt)


@pytest.mark.parametrize("strategy", ["scatter", "fused", "sorted"])
def test_explicit_strategies_match_reference(strategy):
    keys, vals, valid = _case(777, 101, 2, np.int32)
    got = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), 101,
                         op="sum", valid=jnp.asarray(valid),
                         strategy=strategy)
    ref = segment_reduce_ref(jnp.asarray(keys), (jnp.asarray(vals),), 101,
                             op="sum", valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got.values[0]),
                                  np.asarray(ref.values[0]))
    np.testing.assert_array_equal(np.asarray(got.counts),
                                  np.asarray(ref.counts))
    assert int(got.overflow) == int(ref.overflow)


def test_fused_strategy_mixed_dtypes_pytree():
    keys, _, valid = _case(300, 17, 1, np.float32)
    vals = {"f": jnp.asarray(RNG.normal(size=(300, 2)).astype(np.float32)),
            "i": jnp.asarray(RNG.integers(0, 9, 300).astype(np.int32))}
    got = segment_reduce(jnp.asarray(keys), vals, 17, op="sum",
                         valid=jnp.asarray(valid), strategy="fused")
    ref = segment_reduce_ref(jnp.asarray(keys), vals, 17, op="sum",
                             valid=jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(got.values["f"]),
                               np.asarray(ref.values["f"]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.values["i"]),
                                  np.asarray(ref.values["i"]))
    np.testing.assert_array_equal(np.asarray(got.counts),
                                  np.asarray(ref.counts))


def test_tuned_default_matches_reference_and_reports():
    from repro.kernels.segment_reduce import tune_report
    keys, vals, valid = _case(900, 50, 1, np.int32)
    got = segment_reduce(jnp.asarray(keys), (jnp.asarray(vals),), 50,
                         op="sum", valid=jnp.asarray(valid))  # autotuned
    ref = segment_reduce_ref(jnp.asarray(keys), (jnp.asarray(vals),), 50,
                             op="sum", valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got.values[0]),
                                  np.asarray(ref.values[0]))
    entries = [e for e in tune_report() if e["n"] == 900]
    assert entries, "autotuner should have recorded this shape"
    assert entries[0]["candidates"], "candidates should have been timed"
    assert entries[0]["chosen"] in {c["candidate"]
                                    for c in entries[0]["candidates"]}


def test_unknown_strategy_raises():
    with pytest.raises(ValueError, match="unknown segment-reduce strategy"):
        segment_reduce(jnp.zeros((4,), jnp.int32),
                       (jnp.zeros((4,), jnp.float32),), 2,
                       strategy="magic")


# -- the tuner fails loudly and bounds the tiled kernel's dense work ----------

def test_tuner_candidate_that_raises_fails_the_tune(monkeypatch):
    from repro.kernels.segment_reduce import ops as seg_ops
    from repro.kernels.segment_reduce import pick_strategy
    real = seg_ops.segment_reduce_impl

    def broken(*args, strategy, **kw):
        if strategy == "sorted":
            raise RuntimeError("sorted candidate refused")
        return real(*args, strategy=strategy, **kw)

    monkeypatch.setattr(seg_ops, "segment_reduce_impl", broken)
    with pytest.raises(RuntimeError, match="sorted candidate refused"):
        pick_strategy("sum", 333, 7, (jnp.zeros((333,), jnp.int32),))


def test_tuner_times_execution_not_the_callers_trace(monkeypatch):
    import jax
    from repro.kernels.segment_reduce import tune
    seen = []
    real = tune._time_callable

    def spy(fn, *args):
        seen.append(any(isinstance(a, jax.core.Tracer)
                        for a in jax.tree.leaves(args)))
        return real(fn, *args)

    monkeypatch.setattr(tune, "_time_callable", spy)

    @jax.jit
    def program(keys, vals):
        return segment_reduce(keys, (vals,), 11, op="sum").values[0]

    program(jnp.arange(341, dtype=jnp.int32) % 11,
            jnp.ones((341,), jnp.int32))
    assert seen and not any(seen)


@pytest.mark.parametrize("n,num_keys,offered", [
    (1024 * 155 * 1024, 4 ** 6, True),     # smoke k=6 table
    (1024 * 149 * 1024, 4 ** 12, False),   # smoke k=12 table
])
def test_dense_work_bound_gates_tiled(n, num_keys, offered):
    from repro.kernels.segment_reduce import tune
    sig = (((), "int32"),)
    tiled = [c for c in tune._candidates("tpu", "sum", n, num_keys, sig)
             if c[0] == "tiled"]
    assert bool(tiled) is offered
    assert (tune._default_strategy("tpu", n, num_keys)[0] == "tiled") \
        is offered


def test_every_tiling_fits_scoped_vmem():
    from repro.kernels.segment_reduce import tune
    for block, key_block in tune.TILINGS:
        assert tune._vmem_fits(block, key_block, ncols=4)
