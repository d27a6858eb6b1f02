"""Morris counter math: paper Alg. 1/2 semantics + n-fold generalization."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.counters import CMLS8, CMLS16, CMS32, CounterSpec


def test_value_matches_paper_piecewise():
    """Paper Alg. 2: VALUE(0)=0, VALUE(1)=PointValue(1)=1, else (b^c-1)/(b-1)."""
    for c in (CMLS8, CMLS16):
        b = c.base
        states = jnp.arange(0, 40)
        v = np.asarray(c.decode(states))
        assert v[0] == 0.0
        np.testing.assert_allclose(v[1], 1.0, rtol=1e-5)
        expected = (b ** np.arange(0, 40, dtype=np.float64) - 1) / (b - 1)
        np.testing.assert_allclose(v, expected, rtol=2e-4)


@pytest.mark.parametrize("c", [CMLS8, CMLS16], ids=["cmls8", "cmls16"])
def test_decode_eager_equals_jitted(c):
    """Every state decodes to the same float eagerly and inside a jit: the
    read path (eager) and the flush epoch's re-score (fused) must agree bit
    for bit, or a `topk` estimate differs from the `query` answer."""
    states = jnp.arange(1 << c.bits, dtype=jnp.uint32).astype(c.dtype)
    np.testing.assert_array_equal(np.asarray(c.decode(states)),
                                  np.asarray(jax.jit(c.decode)(states)))


def test_increase_prob_is_b_pow_minus_c():
    c = CMLS8
    states = jnp.arange(0, 30)
    p = np.asarray(c.increase_prob(states))
    np.testing.assert_allclose(p, c.base ** -np.arange(0, 30, dtype=np.float64),
                               rtol=1e-5)
    assert (np.asarray(CMS32.increase_prob(states)) == 1.0).all()


def test_nfold_n1_matches_single_increment_probability():
    """nfold with n=1 must increment with exactly P = b^-c (paper Alg. 1)."""
    c = CMLS8
    state = jnp.full((200_000,), 10, jnp.uint8)
    u = jax.random.uniform(jax.random.PRNGKey(0), state.shape)
    new = np.asarray(c.nfold(state, jnp.ones_like(state, jnp.float32), u))
    frac = (new == 11).mean()
    expect = c.base ** -10.0
    assert abs(frac - expect) < 0.01
    assert set(np.unique(new)) <= {10, 11}


def test_nfold_unbiased_in_estimate_space():
    """E[decode(nfold(c, n))] ~ decode(c) + n across n and c."""
    c = CMLS8
    for state, n in [(0, 7), (5, 3), (20, 100), (40, 1000)]:
        s = jnp.full((100_000,), state, jnp.uint8)
        u = jax.random.uniform(jax.random.PRNGKey(state + n), s.shape)
        new = c.nfold(s, jnp.full(s.shape, n, jnp.float32), u)
        mean_est = float(c.decode(new).mean())
        target = float(c.decode(jnp.asarray(state, jnp.uint8))) + n
        assert abs(mean_est - target) / target < 0.02, (state, n, mean_est)


def test_nfold_zero_is_identity():
    c = CMLS16
    s = jnp.arange(0, 1000, dtype=jnp.uint16)
    u = jax.random.uniform(jax.random.PRNGKey(0), s.shape)
    new = c.nfold(s, jnp.zeros(s.shape), u)
    assert (np.asarray(new) == np.asarray(s)).all()


def test_saturation_at_max_state():
    c = CMLS8
    s = jnp.full((100,), c.max_state, jnp.uint8)
    new = c.nfold(s, jnp.full((100,), 1e9, jnp.float32),
                  jnp.zeros((100,)))
    assert (np.asarray(new) == c.max_state).all()


def test_linear_nfold_exact_past_float32_precision():
    """CMS32 linear cells are exact in integer space: states past 2^24
    round in float32, so the old estimate-space path drifted from its own
    uint32 state.  The integer path must land s + n exactly."""
    c = CMS32
    s0 = 1 << 24
    s = jnp.asarray([s0, s0 + 1, s0 + 3, 0], jnp.uint32)
    n = jnp.asarray([3.0, 5.0, 1.0, float(1 << 25)], jnp.float32)
    new = np.asarray(c.nfold(s, n, jnp.zeros((4,))))
    np.testing.assert_array_equal(new, [s0 + 3, s0 + 6, s0 + 4, 1 << 25])


def test_linear_nfold_saturates_and_rounds_fraction():
    c = CMS32
    # room-clamped saturation at max_state, no uint32 wraparound
    s = jnp.asarray([c.max_state - 2, c.max_state], jnp.uint32)
    new = np.asarray(c.nfold(s, jnp.asarray([10.0, 1e12], jnp.float32),
                             jnp.zeros((2,))))
    assert (new == c.max_state).all()
    # fractional n: stochastic bump with P = frac
    s = jnp.full((100_000,), 7, jnp.uint32)
    u = jax.random.uniform(jax.random.PRNGKey(1), s.shape)
    new = np.asarray(c.nfold(s, jnp.full(s.shape, 2.25, jnp.float32), u))
    assert set(np.unique(new)) == {9, 10}
    assert abs((new == 10).mean() - 0.25) < 0.01


@pytest.mark.parametrize("c", [CMLS8, CMLS16], ids=["cmls8", "cmls16"])
def test_encode_floor_inverts_decode(c):
    """Every state comes back exactly: float roundoff in the log must not
    land one state short of Value(c) <= Value(c)."""
    states = jnp.arange(1 << c.bits, dtype=jnp.uint32).astype(c.dtype)
    back = np.asarray(c.encode_floor(c.decode(states)))
    np.testing.assert_array_equal(back, np.asarray(states, np.float32))


def test_max_value_matches_bits():
    assert CMLS8.max_state == 255
    assert CMLS16.max_state == 65535
    assert CMLS8.max_value == pytest.approx(
        (math.expm1(255 * math.log(1.08))) / 0.08, rel=1e-6)


def test_invalid_specs_raise():
    with pytest.raises(ValueError):
        CounterSpec(kind="log", base=0.5)
    with pytest.raises(ValueError):
        CounterSpec(kind="wat")
    with pytest.raises(ValueError):
        CounterSpec(bits=12)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 250), st.integers(0, 10_000), st.floats(0, 1))
def test_property_nfold_monotone_and_bounded(state, n, u):
    """State never decreases; never exceeds encode(v+n)+1."""
    c = CMLS8
    s = jnp.asarray([state], jnp.uint8)
    new = int(c.nfold(s, jnp.asarray([float(n)]), jnp.asarray([u]))[0])
    assert new >= state
    v2 = float(c.decode(s)[0]) + n
    upper = int(np.asarray(c.encode_floor(jnp.asarray([v2])))[0]) + 1
    assert new <= min(upper, c.max_state)
