"""Validation of the trip-count-aware HLO cost parser: scanned graphs must
match the unrolled graph's cost_analysis (which XLA counts correctly)."""
import pytest


@pytest.fixture(scope="module")
def jax_mod():
    import jax
    return jax


def test_scan_flops_match_unrolled(jax_mod):
    import jax
    import jax.numpy as jnp
    from repro.roofline.hlo_cost import analyze_hlo

    def f_scan(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    def f_unroll(x):
        for _ in range(7):
            x = jnp.tanh(x @ x)
        return x

    xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c_scan = jax.jit(f_scan).lower(xs).compile()
    c_un = jax.jit(f_unroll).lower(xs).compile()
    t_scan = analyze_hlo(c_scan.as_text())
    t_un = analyze_hlo(c_un.as_text())
    expected = 7 * 2 * 64 ** 3
    assert abs(t_scan.flops - t_un.flops) / t_un.flops < 0.05
    assert t_scan.flops >= expected
    # XLA's own analysis undercounts the scan ~7x
    xla_cost = dict(c_scan.cost_analysis())
    assert xla_cost["flops"] < t_scan.flops / 3


def test_nested_scan_multiplies(jax_mod):
    import jax
    import jax.numpy as jnp
    from repro.roofline.hlo_cost import analyze_hlo

    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    xs = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    t = analyze_hlo(jax.jit(f).lower(xs).compile().as_text())
    expected = 15 * 2 * 32 ** 3
    assert 0.9 * expected <= t.flops <= 1.3 * expected


def test_dus_counts_slice_not_stack(jax_mod):
    import jax
    import jax.numpy as jnp
    from repro.roofline.hlo_cost import analyze_hlo

    def f(x):
        buf = jnp.zeros((64, 32, 32), x.dtype)

        def body(carry, i):
            buf, x = carry
            x = x * 1.5
            buf = jax.lax.dynamic_update_slice(buf, x[None], (i, 0, 0))
            return (buf, x), None
        (buf, _), _ = jax.lax.scan(f=body, init=(buf, x),
                                   xs=jnp.arange(64))
        return buf

    xs = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    t = analyze_hlo(jax.jit(f).lower(xs).compile().as_text())
    stack_bytes = 64 * 32 * 32 * 4
    # if the DUS were charged at full-stack size per iteration we'd see
    # >= 64 * stack_bytes; slice-aware accounting stays far below
    assert t.bytes < 16 * stack_bytes, t.bytes
