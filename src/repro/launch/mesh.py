"""Mesh construction.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init; smoke
tests and benchmarks see the real 1-CPU platform).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with `Auto` axes on every dimension.

    jax 0.9's `make_mesh` defaults to `Explicit` axes, under which an
    indexing gather such as `keys[order]` inside `shard_map` needs the
    mesh entered via `jax.set_mesh`.  The sharded sketch code is written
    for `Auto` axes (the compiler propagates shardings), so every mesh in
    this repo is built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading
    "pod" axis (data parallelism across the cross-pod links)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (smoke tests, examples)."""
    n = jax.device_count()
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
