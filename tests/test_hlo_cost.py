"""Regression tests for the trip-count-aware HLO cost walker — the
roofline numbers depend on it (launch/hlo_cost.py)."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze


def test_matmul_flops_exact():
    a = jnp.zeros((256, 256), jnp.float32)
    c = jax.jit(lambda a, b: a @ b).lower(a, a).compile()
    r = analyze(c.as_text())
    assert abs(r["flops"] - 2 * 256 ** 3) / (2 * 256 ** 3) < 0.01


def test_scan_trip_count_multiplies():
    """XLA cost_analysis counts a while body once; the walker must
    multiply by the trip count (scan-of-13 == unrolled-13)."""
    a = jnp.zeros((128, 128), jnp.float32)

    def scanned(a):
        def body(x, _):
            return jnp.tanh(x @ a), None
        x, _ = jax.lax.scan(body, a, None, length=13)
        return x

    def unrolled(a):
        x = a
        for _ in range(13):
            x = jnp.tanh(x @ a)
        return x

    fs = analyze(jax.jit(scanned).lower(a).compile().as_text())["flops"]
    fu = analyze(jax.jit(unrolled).lower(a).compile().as_text())["flops"]
    xla = jax.jit(scanned).lower(a).compile().cost_analysis()["flops"]
    assert abs(fs - fu) / fu < 0.02
    assert xla < fs / 5          # demonstrates the undercount being fixed


def test_nested_scan():
    a = jnp.zeros((64, 64), jnp.float32)

    def nested(a):
        def outer(x, _):
            def inner(y, _):
                return y @ a, None
            y, _ = jax.lax.scan(inner, x, None, length=4)
            return y, None
        x, _ = jax.lax.scan(outer, a, None, length=3)
        return x

    r = analyze(jax.jit(nested).lower(a).compile().as_text())
    expect = 12 * 2 * 64 ** 3
    assert abs(r["flops"] - expect) / expect < 0.05


def test_slice_bytes_not_full_buffer():
    """dynamic-slice of a big stacked buffer must count the slice, not
    the stack (the per-layer weight slicing pattern)."""
    w = jnp.zeros((30, 256, 256), jnp.float32)
    x = jnp.zeros((4, 256), jnp.float32)

    def f(w, x):
        def body(x, wi):
            return x @ wi, None
        x, _ = jax.lax.scan(body, x, w)
        return x

    r = analyze(jax.jit(f).lower(w, x).compile().as_text())
    # full-stack-per-iteration would be 30 * 7.8MB = 236MB; actual
    # traffic is ~30 * (slice 256KB + x 4KB) ≈ 8MB
    assert r["bytes"] < 60e6, r["bytes"]
