"""The key-routed cell on four host devices (run with the other cell tests:
`PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/cells`).

`web1t_routed4.ingest`, cut by its `cpu_cut`, runs through
`harness.run_cell` in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4, so its planes are
sharded over a mesh of four devices as on the chip: the sound run comes out
correct; the control counter, and the routed epoch broken underneath, do
not.  The faults are planted in the exchange the epoch traces:

  * one shard's received keys dropped (their weights zeroed at shard 0);
  * keys routed by the sketch's first row hash in place of `route_of`, so
    the epoch lands them on shards the reads do not ask.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

# test_cells puts this directory and src/ on the path
from test_cells import HERE, ROOT

CELL = "web1t_routed4.ingest"

RUNS = r"""
import json, sys
sys.path.insert(0, HERE)
import jax
import jax.numpy as jnp
import test_cells
from repro.core import sharded
from repro.core.hashing import host_row_seeds, row_hashes

assert jax.device_count() == 4
cfg, _ = test_cells.tiny(CELL)
seeds = jnp.asarray(host_row_seeds(int(cfg["sketch_seed"]), 2), jnp.uint32)
width = int(cfg["planes"][0]["width"])
exchange = sharded.routed_exchange


def shard_dropped(keys, weights, axis_name):
    k, w, d = exchange(keys, weights, axis_name)
    return k, jnp.where(jax.lax.axis_index(axis_name) == 0, 0.0, w), d


def by_row_hash(keys, weights, axis_name):
    route_of = sharded.route_of
    sharded.route_of = lambda k, n: (row_hashes(
        k.reshape(-1), seeds, width)[0].reshape(k.shape) % n)
    try:
        return exchange(keys, weights, axis_name)
    finally:
        sharded.route_of = route_of


out = {}
for name, control, fault in (("sound", False, None),
                             ("control", True, None),
                             ("shard_dropped", False, shard_dropped),
                             ("row_hash_routing", False, by_row_hash)):
    sharded.routed_exchange = fault or exchange
    got = test_cells.run_tiny(CELL, control=control)
    sharded.routed_exchange = exchange
    out[name] = {"correct": got["correct"],
                 "checks": {k: v["value"] for k, v in got["checks"].items()},
                 "devices": got["device"]["count"]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = f"HERE = {str(HERE)!r}\nCELL = {CELL!r}\n" + textwrap.dedent(RUNS)
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_run_on_four_devices_is_correct(runs):
    assert runs["sound"]["devices"] == 4
    assert runs["sound"]["correct"], runs["sound"]["checks"]
    assert runs["sound"]["checks"]["dropped"] == 0


def test_control_is_not_correct(runs):
    assert not runs["control"]["correct"], runs["control"]["checks"]


@pytest.mark.parametrize("fault", ["shard_dropped", "row_hash_routing"])
def test_broken_routing_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]
