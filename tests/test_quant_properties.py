"""Hypothesis property tests for the quantization invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (see requirements-dev.txt); "
           "property tests run where dev deps are present")
from hypothesis import example, given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.quant import (QuantConfig, W8_SYM_CHANNEL, dequantize, pack_int4,
                         quantize, quantize_values, unpack_int4)

finite_f32 = st.floats(min_value=-1e4, max_value=1e4, width=32,
                       allow_nan=False, allow_infinity=False)


def arrays(min_rows=2, max_rows=16):
    return hnp.arrays(np.float32,
                      st.tuples(st.integers(min_rows, max_rows).map(lambda r: 2 * r),
                                st.integers(1, 12)),
                      elements=finite_f32)


@given(arrays())
@settings(max_examples=60, deadline=None)
def test_quant_error_bounded_by_half_scale(x):
    """|x - dq(q(x))| <= scale/2 element-wise (symmetric, per-channel)."""
    xj = jnp.asarray(x)
    q, scale, _ = quantize_values(xj, W8_SYM_CHANNEL)
    xhat = q.astype(jnp.float32) * scale
    bound = jnp.broadcast_to(scale, xj.shape) * 0.5001 + 1e-7
    assert bool(jnp.all(jnp.abs(xj - xhat) <= bound))


@given(arrays())
@settings(max_examples=60, deadline=None)
def test_quant_values_in_range(x):
    for cfg in (W8_SYM_CHANNEL,
                QuantConfig(bits=4, symmetric=True, granularity="tensor"),
                QuantConfig(bits=8, symmetric=False, granularity="tensor")):
        q, _, _ = quantize_values(jnp.asarray(x), cfg)
        assert int(q.min()) >= cfg.qmin
        assert int(q.max()) <= cfg.qmax


@given(hnp.arrays(np.int8, st.tuples(st.integers(1, 16).map(lambda r: 2 * r),
                                     st.integers(1, 16)),
                  elements=st.integers(-8, 7)))
@settings(max_examples=60, deadline=None)
def test_pack_unpack_is_identity(q):
    qj = jnp.asarray(q)
    assert bool(jnp.all(unpack_int4(pack_int4(qj)) == qj))


@given(arrays(), st.floats(0.1, 50.0))
# a channel at the 1e-8 scale floor next to one clear of it
@example(np.array([[1e-8, 1.0]] * 4, np.float32), 0.5)
@settings(max_examples=40, deadline=None)
def test_symmetric_quant_scale_equivariant(x, c):
    """q(c*x) == q(x) for symmetric quantization (scale absorbs c)."""
    xj = jnp.asarray(x)
    # per-channel scales are floored at 1e-8 (compute_scale_zero), so
    # only channels clear of that floor at both scales (by 100x) are
    # equivariant
    live = np.asarray(jnp.max(jnp.abs(xj), axis=0)) * min(c, 1.0) > 1e-6
    if not live.any():
        return
    q1, _, _ = quantize_values(xj, W8_SYM_CHANNEL)
    q2, _, _ = quantize_values(xj * c, W8_SYM_CHANNEL)
    diff = jnp.abs(q1.astype(jnp.int32) - q2.astype(jnp.int32))[:, live]
    # allow off-by-one from rounding at the scaled boundary
    assert int(jnp.max(diff)) <= 1


@given(arrays())
@settings(max_examples=40, deadline=None)
def test_dequantize_quantize_fixed_point(x):
    """quantize∘dequantize is a fixed point: re-quantizing a dequantized
    tensor reproduces the same integers (idempotence of the lattice)."""
    xj = jnp.asarray(x)
    t = quantize(xj, W8_SYM_CHANNEL)
    xhat = dequantize(t)
    t2 = quantize(xhat, W8_SYM_CHANNEL)
    d1 = dequantize(t)
    d2 = dequantize(t2)
    assert bool(jnp.all(jnp.abs(d1 - d2) <= 1e-5 + 1e-3 * jnp.abs(d1)))
