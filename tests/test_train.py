"""Training substrate: optimizer math, checkpointing, compression, loop."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.train import checkpoint as C
from repro.train import compression as Z
from repro.train import loop as L
from repro.train.optimizer import (OptimizerConfig, clip_by_global_norm,
                                   lr_schedule, make_optimizer)


def test_adamw_matches_reference_math():
    cfg = OptimizerConfig(peak_lr=0.1, warmup_steps=0, decay_steps=10**9,
                          b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                          grad_clip=1e9)
    init, update = make_optimizer(cfg, label_fn=lambda p: "dense")
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.25])}
    state = init(p)
    new_p, _, _ = update(g, state, p, jnp.asarray(0))
    # step 1: mu_hat = g, nu_hat = g^2 -> update = g/(|g|+eps) = sign(g)
    expect = np.asarray(p["w"]) - 0.1 * np.sign(np.asarray(g["w"]))
    np.testing.assert_allclose(np.asarray(new_p["w"]), expect, atol=1e-5)


def test_rowwise_adagrad_math():
    cfg = OptimizerConfig(table_lr=1.0, table_eps=0.0, grad_clip=1e9)
    init, update = make_optimizer(cfg, label_fn=lambda p: "table")
    p = {"t": jnp.ones((2, 4))}
    g = {"t": jnp.asarray([[2.0, 2.0, 2.0, 2.0], [0.0, 0.0, 0.0, 0.0]])}
    state = init(p)
    assert state["t"]["acc"].shape == (2,)
    new_p, new_s, _ = update(g, state, p, jnp.asarray(0))
    # row 0: acc = mean(4)=4 -> update = g/sqrt(4) = 1 -> p = 0
    np.testing.assert_allclose(np.asarray(new_p["t"][0]), 0.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(new_p["t"][1]), 1.0)  # untouched


def test_lr_schedule_shape():
    cfg = OptimizerConfig(peak_lr=1.0, warmup_steps=10, decay_steps=110,
                          min_lr_ratio=0.1)
    assert float(lr_schedule(cfg, jnp.asarray(0))) == 0.0
    assert float(lr_schedule(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(lr_schedule(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(lr_schedule(cfg, jnp.asarray(110))) == pytest.approx(0.1)
    mid = float(lr_schedule(cfg, jnp.asarray(60)))
    assert 0.1 < mid < 1.0


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(5.0)
    total = jnp.sqrt(clipped["a"][0] ** 2 + clipped["b"][0] ** 2)
    assert float(total) == pytest.approx(1.0, rel=1e-5)


def test_checkpoint_roundtrip_and_gc(tmp_path):
    root = str(tmp_path / "ck")
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "n": {"b": jnp.asarray([1, 2])}}
    for step in (1, 2, 3, 4, 5):
        C.save(root, step, tree, keep_last=2)
    assert C.latest_step(root) == 5
    kept = sorted(os.listdir(root))
    assert kept == ["step_00000004", "step_00000005"]
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored, manifest = C.restore(root, like)
    assert manifest["step"] == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(restored["n"]["b"]),
                                  np.asarray(tree["n"]["b"]))


def test_checkpoint_atomicity_no_tmp_left(tmp_path):
    root = str(tmp_path / "ck")
    C.save(root, 7, {"x": jnp.zeros(3)})
    assert not any(d.endswith(".tmp") for d in os.listdir(root))


def test_checkpoint_elastic_restore_resharding(tmp_path):
    """Restore places leaves per the TARGET sharding (mesh-independent)."""
    from jax.sharding import NamedSharding, PartitionSpec
    root = str(tmp_path / "ck")
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    C.save(root, 1, tree)
    mesh = make_mesh((1,), ("data",))
    target = {"w": jax.ShapeDtypeStruct(
        (4, 4), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("data", None)))}
    restored, _ = C.restore(root, target)
    assert restored["w"].sharding.spec == PartitionSpec("data", None)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_quantize_dequantize_error_bound():
    g = jax.random.normal(jax.random.PRNGKey(0), (5000,)) * 3.0
    q, scale, n = Z.quantize(g)
    back = Z.dequantize(q, scale, n, g.shape)
    err = jnp.abs(back - g).max()
    assert float(err) <= float(jnp.abs(g).max()) / 127.0 + 1e-6


def test_error_feedback_is_asymptotically_unbiased():
    """Summed compressed grads track summed true grads (EF residual)."""
    rng = jax.random.PRNGKey(1)
    residual = jnp.zeros((1000,))
    total_true = jnp.zeros((1000,))
    total_sent = jnp.zeros((1000,))
    for i in range(30):
        rng, k = jax.random.split(rng)
        g = jax.random.normal(k, (1000,))
        sent, residual = Z.compress_with_feedback(g, residual)
        total_true += g
        total_sent += sent
    # residual bounds the gap: |sum sent - sum true| = |residual|
    np.testing.assert_allclose(np.asarray(total_sent + residual),
                               np.asarray(total_true), rtol=1e-4, atol=1e-4)


def test_loop_restores_and_fast_forwards(tmp_path):
    calls = []

    def loss(p, batch, rng):
        return (p["w"] ** 2).sum(), {}

    init, step = L.make_train_step(loss, OptimizerConfig(peak_lr=0.01,
                                                         warmup_steps=0,
                                                         decay_steps=100))
    state = init({"w": jnp.ones(3)}, jax.random.PRNGKey(0))
    batches = ((s, {}) for s in range(100))
    root = str(tmp_path / "ck")
    st1 = L.run(state, step, batches, n_steps=6, ckpt_dir=root, ckpt_every=3,
                log_every=0, log_fn=calls.append)
    time.sleep(0.5)  # async save
    assert C.latest_step(root) == 6
    # new process restart: same init, must restore to step 6 and do nothing
    state2 = init({"w": jnp.ones(3)}, jax.random.PRNGKey(0))
    batches2 = ((s, {}) for s in range(100))
    st2 = L.run(state2, step, batches2, n_steps=6, ckpt_dir=root,
                log_every=0, log_fn=calls.append)
    np.testing.assert_allclose(np.asarray(st1.params["w"]),
                               np.asarray(st2.params["w"]), rtol=1e-6)
