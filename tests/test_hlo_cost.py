"""The loop-aware HLO cost model (launch/hlo_cost.py) against programs
with analytically known FLOP counts."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import loop_aware_costs


def _costs(fn, *specs):
    return loop_aware_costs(jax.jit(fn).lower(*specs).compile().as_text())


def test_single_matmul_exact():
    m, k, n = 64, 128, 32
    t = _costs(lambda a, b: a @ b,
               jax.ShapeDtypeStruct((m, k), jnp.float32),
               jax.ShapeDtypeStruct((k, n), jnp.float32))
    assert t.flops == pytest.approx(2 * m * k * n, rel=1e-6)


def test_scan_multiplies_by_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=13)
        return y

    t = _costs(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
               jax.ShapeDtypeStruct((32, 32), jnp.float32))
    assert t.flops == pytest.approx(13 * 2 * 32 ** 3, rel=0.05)


def test_nested_scan_composes():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=7)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    t = _costs(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
               jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert t.flops == pytest.approx(35 * 2 * 64 ** 3, rel=0.05)


def test_scanned_equals_unrolled():
    """The invariance XLA's own cost_analysis lacks."""
    def block(x, w1, w2):
        return x + jnp.maximum(x @ w1, 0) @ w2

    def scanned(x, w1s, w2s):
        def body(c, ws):
            return block(c, ws[0], ws[1]), None
        y, _ = jax.lax.scan(body, x, (w1s, w2s))
        return y

    def unrolled(x, w1s, w2s):
        for i in range(6):
            x = block(x, w1s[i], w2s[i])
        return x

    xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w1 = jax.ShapeDtypeStruct((6, 64, 128), jnp.float32)
    w2 = jax.ShapeDtypeStruct((6, 128, 64), jnp.float32)
    ts = _costs(scanned, xs, w1, w2)
    tu = _costs(unrolled, xs, w1, w2)
    assert ts.flops == pytest.approx(tu.flops, rel=0.02)
    exact = 6 * (2 * 32 * 64 * 128 * 2)
    assert ts.flops == pytest.approx(exact, rel=0.02)


def test_remat_counted():
    """jax.checkpoint recompute shows up as extra FLOPs in the backward."""
    def loss(x, w):
        h = jax.checkpoint(lambda a: jnp.tanh(a @ w))(x)
        return jnp.sum(h * h)

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    t_fwd = _costs(loss, x, w)
    t_grad = _costs(jax.grad(loss, argnums=(0, 1)), x, w)
    # grad ≥ fwd + 2 backward matmuls (recompute may be CSE'd for this
    # single-matmul body)
    assert t_grad.flops >= 2.9 * t_fwd.flops


def test_collectives_scale_with_loop(monkeypatch):
    """A psum inside a scanned shard_map body counts trip_count times."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def inner(a):
        return jax.lax.psum(a, "x")

    def f(a):
        sm = jax.shard_map(inner, mesh=mesh, in_specs=P("x"),
                           out_specs=P())

        def body(c, _):
            return c + sm(c), None
        y, _ = jax.lax.scan(body, a, None, length=9)
        return y

    with mesh:
        t = _costs(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    # 9 iterations × 8 floats × 4B = 288 bytes of all-reduce
    assert t.collective_bytes == pytest.approx(9 * 8 * 4, rel=0.1) or \
        t.collective_bytes == 0.0   # single-device AR may be elided


def test_peak_rates_unknown_device_kind_raises():
    """Roofline seconds are priced per chip kind; a chip missing from the
    peak table is an error, never a silent v5e default."""
    from repro.launch.analysis import RooflineTerms
    from repro.launch.mesh import V5E, chip_peaks
    assert chip_peaks(V5E) == {"flops_bf16": 197e12, "hbm_bw": 819e9,
                               "ici_bw": 50e9}
    with pytest.raises(ValueError, match="no peak rates"):
        chip_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no peak rates"):
        RooflineTerms(flops=1.0, hbm_bytes=1.0, collective_bytes=0.0,
                      device_kind="cpu")
    t = RooflineTerms(flops=197e12, hbm_bytes=819e9, collective_bytes=0.0)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
