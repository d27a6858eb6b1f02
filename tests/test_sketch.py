"""Sketch core invariants: unit + property-based (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (CMLS8, CMLS16, CMS32, CounterSpec, Sketch,
                        SketchSpec, init, merge, query, query_state,
                        update_batched, update_exact)

VARIANTS = [CMS32, CMLS16, CMLS8]


def _zipf_keys(n=4000, vocab=1500, seed=0):
    return jnp.asarray((np.random.default_rng(seed).zipf(1.3, n) % vocab)
                       .astype(np.uint32))


@pytest.mark.parametrize("counter", VARIANTS, ids=["cms32", "cmls16", "cmls8"])
@pytest.mark.parametrize("mode", ["exact", "batched"])
def test_counts_track_truth(counter, mode):
    keys = _zipf_keys()
    spec = SketchSpec(width=4096, depth=4, counter=counter)
    s = init(spec)
    if mode == "exact":
        s = update_exact(s, keys, jax.random.PRNGKey(0))
    else:
        s = update_batched(s, keys, jax.random.PRNGKey(0))
    uniq, true = np.unique(np.asarray(keys), return_counts=True)
    est = np.asarray(query(s, jnp.asarray(uniq)))
    are = np.mean(np.abs(est - true) / true)
    assert are < 0.35, f"{counter.kind}:{mode} ARE={are}"
    # heavy hitters must be tight
    top = true >= 50
    if top.any():
        rel = np.abs(est[top] - true[top]) / true[top]
        assert rel.mean() < 0.15


def test_cms_never_underestimates():
    """Classic CMS-CU guarantee (only holds for deterministic counters)."""
    keys = _zipf_keys(seed=3)
    spec = SketchSpec(width=512, depth=4, counter=CMS32)  # heavy collisions
    s = update_exact(init(spec), keys, jax.random.PRNGKey(0))
    uniq, true = np.unique(np.asarray(keys), return_counts=True)
    est = np.asarray(query(s, jnp.asarray(uniq)))
    assert (est >= true - 1e-6).all()


def test_unseen_keys_zero_when_uncrowded():
    spec = SketchSpec(width=1 << 16, depth=4, counter=CMLS16)
    s = update_batched(init(spec), _zipf_keys(500, 200), jax.random.PRNGKey(0))
    unseen = jnp.arange(10_000, 10_100, dtype=jnp.uint32)
    est = np.asarray(query(s, unseen))
    assert (est <= 1.0).mean() > 0.95  # w >> items: collisions ~ absent


def test_update_monotone():
    """More observations never decrease any cell (conservative update)."""
    spec = SketchSpec(width=256, depth=2, counter=CMLS8)
    s0 = init(spec)
    keys = _zipf_keys(1000, 300, seed=1)
    s1 = update_batched(s0, keys[:500], jax.random.PRNGKey(1))
    s2 = update_batched(s1, keys[500:], jax.random.PRNGKey(2))
    assert (np.asarray(s2.table) >= np.asarray(s1.table)).all()
    assert (np.asarray(s1.table) >= np.asarray(s0.table)).all()


@pytest.mark.parametrize("counter", VARIANTS, ids=["cms32", "cmls16", "cmls8"])
def test_merge_max_is_mergeable_summary(counter):
    """query(merge(a,b)) >= max(query(a), query(b)) elementwise."""
    spec = SketchSpec(width=2048, depth=3, counter=counter)
    ka, kb = _zipf_keys(seed=4), _zipf_keys(seed=5)
    sa = update_batched(init(spec), ka, jax.random.PRNGKey(4))
    sb = update_batched(init(spec), kb, jax.random.PRNGKey(5))
    m = merge(sa, sb, mode="max")
    probe = jnp.arange(1500, dtype=jnp.uint32)
    qa, qb, qm = (np.asarray(query(x, probe)) for x in (sa, sb, m))
    assert (qm >= np.maximum(qa, qb) - 1e-5).all()


def test_merge_estimate_sum_approximates_union():
    spec = SketchSpec(width=1 << 15, depth=2, counter=CMLS16)
    ka, kb = _zipf_keys(seed=6), _zipf_keys(seed=7)
    sa = update_batched(init(spec), ka, jax.random.PRNGKey(6))
    sb = update_batched(init(spec), kb, jax.random.PRNGKey(7))
    m = merge(sa, sb, mode="estimate_sum", rng=jax.random.PRNGKey(8))
    allk = np.concatenate([np.asarray(ka), np.asarray(kb)])
    uniq, true = np.unique(allk, return_counts=True)
    est = np.asarray(query(m, jnp.asarray(uniq)))
    mask = true >= 20
    rel = np.abs(est[mask] - true[mask]) / true[mask]
    assert rel.mean() < 0.2


def test_merge_estimate_sum_stochastic_rounding_unbiased():
    """With an rng, estimate_sum's stochastic re-encode preserves the mean:
    E[decode(merge(a, b))] == decode(a) + decode(b) cell-for-cell."""
    spec = SketchSpec(width=128, depth=1, counter=CMLS8)
    # fixed, representable states so the target sum is exact and the
    # re-encode actually has a fractional residue to round
    ta = jnp.full((1, 128), 30, jnp.uint8)
    tb = jnp.full((1, 128), 25, jnp.uint8)
    a, b = Sketch(table=ta, spec=spec), Sketch(table=tb, spec=spec)
    c = spec.counter
    target = float(c.decode(ta[0, 0]) + c.decode(tb[0, 0]))
    draws = np.stack([
        np.asarray(c.decode(merge(a, b, mode="estimate_sum",
                                  rng=jax.random.PRNGKey(i)).table))
        for i in range(64)])  # 64 rngs x 128 cells = 8192 samples
    mean = draws.mean()
    assert abs(mean - target) / target < 0.01, (mean, target)
    # floor mode (no rng) deterministically under-shoots by < one step
    lo = float(c.decode(merge(a, b, mode="estimate_sum").table[0, 0]))
    assert lo <= target < lo + float(c.point_mass(
        merge(a, b, mode="estimate_sum").table[0, 0].astype(jnp.float32) + 1))


def test_merge_spec_mismatch_raises():
    a = init(SketchSpec(width=128, depth=2, counter=CMLS8))
    b = init(SketchSpec(width=256, depth=2, counter=CMLS8))
    with pytest.raises(ValueError):
        merge(a, b)


def test_spec_refuses_width_past_int32_columns():
    """`row_hashes` casts columns to int32: a row of 2^31 cells or more
    would index the wrong cells, so the spec refuses it up front."""
    SketchSpec(width=2 ** 31 - 128)
    for width in (2 ** 31, 2 ** 32 + 128):
        with pytest.raises(ValueError, match="row_hashes"):
            SketchSpec(width=width)


def test_sketch_is_checkpointable_pytree():
    s = update_batched(init(SketchSpec(width=128, depth=2)),
                       _zipf_keys(100, 50), jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(s)
    s2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (np.asarray(s2.table) == np.asarray(s.table)).all()


# ---------------------------------------------------------------------------
# property-based (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=300),
       st.sampled_from([0, 1, 2]))
def test_property_linear_exact_counts_when_wide(keys, variant_seed):
    """A wide linear CU sketch with few items counts exactly."""
    keys = jnp.asarray(np.asarray(keys, np.uint32))
    spec = SketchSpec(width=1 << 14, depth=4, counter=CMS32, seed=variant_seed)
    s = update_exact(init(spec), keys, jax.random.PRNGKey(0))
    uniq, true = np.unique(np.asarray(keys), return_counts=True)
    est = np.asarray(query(s, jnp.asarray(uniq)))
    # collisions possible but vanishingly rare at this width/count
    assert (est >= true - 1e-6).all()
    assert np.mean(est == true) > 0.98


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 200))
def test_property_single_key_estimate_unbiased_ish(key, n):
    """Repeating one key n times: log-counter estimate ~ n in expectation."""
    keys = jnp.full((n,), key, jnp.uint32)
    spec = SketchSpec(width=512, depth=2, counter=CMLS8)
    ests = []
    for i in range(8):
        s = update_batched(init(spec), keys, jax.random.PRNGKey(i))
        ests.append(float(query(s, jnp.asarray([key], jnp.uint32))[0]))
    mean = np.mean(ests)
    assert mean >= n * 0.5 and mean <= n * 2.0 + 2.0


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 500), min_size=2, max_size=200))
def test_property_batched_vs_exact_same_support(keys):
    """Batched and exact updates agree on which cells are touched."""
    keys = jnp.asarray(np.asarray(keys, np.uint32))
    spec = SketchSpec(width=1 << 12, depth=3, counter=CMS32)
    se = update_exact(init(spec), keys, jax.random.PRNGKey(0))
    sb = update_batched(init(spec), keys, jax.random.PRNGKey(1))
    assert ((np.asarray(se.table) > 0) == (np.asarray(sb.table) > 0)).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 64))
def test_property_query_state_is_min_over_rows(seed, depth):
    depth = min(depth, 8)
    spec = SketchSpec(width=257, depth=depth, counter=CMLS8, seed=seed)
    keys = _zipf_keys(300, 100, seed=seed % 97)
    s = update_batched(init(spec), keys, jax.random.PRNGKey(0))
    probe = jnp.arange(50, dtype=jnp.uint32)
    from repro.core.hashing import make_row_seeds, row_hashes
    cols = row_hashes(probe, make_row_seeds(seed, depth), 257)
    manual = np.asarray(s.table)[np.arange(depth)[:, None], np.asarray(cols)].min(0)
    assert (np.asarray(query_state(s, probe)) == manual).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**63), st.integers(1, 16))
def test_property_host_row_seeds_match_device(seed, depth):
    """The host-side (trace-safe) seed derivation is bit-identical to the
    jnp one — the kernel wrappers rely on this to cache seeds per spec."""
    from repro.core.hashing import host_row_seeds, make_row_seeds
    got = host_row_seeds(seed, depth)
    want = tuple(int(x) for x in np.asarray(make_row_seeds(seed, depth)))
    assert got == want
