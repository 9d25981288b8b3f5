"""Asynchronous checkpointing: roundtrip, stall behavior, atomicity,
RAM-cache fast restore, elastic (re-sharded) load."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ft.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
from repro.utils import tree_allclose


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (64, 32)),
            "opt": {"m": jnp.ones((64, 32)), "step": jnp.int32(7)}}


def test_roundtrip_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = _state()
    stall = mgr.save_async(10, state, extra={"data_step": 11})
    mgr.wait()
    assert stall < 5.0
    restored, extra = mgr.restore(10, jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x), state))
    assert tree_allclose(state, restored)
    assert extra["data_step"] == 11


def test_restore_from_disk_after_cache_eviction(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=8, ram_cache_slots=1)
    states = {s: _state(s) for s in (1, 2, 3)}
    for s, st in states.items():
        mgr.save_async(s, st)
    mgr.wait()
    assert list(mgr.ram_cache) == [3]          # evicted down to 1 slot
    template = jax.tree_util.tree_map(jnp.zeros_like, states[1])
    restored, _ = mgr.restore(1, template)     # must come from disk
    assert tree_allclose(states[1], restored)


def test_keep_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in range(5):
        mgr.save_sync(s, _state(s))
    assert mgr.available_steps() == [3, 4]


def test_latest_restorable_prefers_ram(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3,
                            storage_bandwidth_gbps=0.01)  # slow persist
    mgr.save_async(5, _state())
    # persist is still in flight; RAM cache must already expose step 5
    assert mgr.latest_restorable() == 5
    mgr.wait(timeout=60)
    assert mgr.latest_step() == 5


def test_async_stall_much_smaller_than_sync(tmp_path):
    """The paper's §6.1 claim in miniature: async checkpointing blocks for
    the host snapshot only, not the (throttled) storage write."""
    big = {"w": jnp.ones((512, 1024))}          # 2 MiB
    mgr = CheckpointManager(str(tmp_path), keep=2,
                            storage_bandwidth_gbps=0.05)   # ~0.3s write
    t_sync = mgr.save_sync(1, big)
    t_async = mgr.save_async(2, big)
    mgr.wait(timeout=60)
    assert t_async < t_sync / 3, (t_sync, t_async)


def test_atomic_commit_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=4)
    for s in range(3):
        mgr.save_async(s, _state(s))
    mgr.wait()
    for name in os.listdir(tmp_path):
        assert not name.endswith(".tmp")
        assert os.path.exists(os.path.join(tmp_path, name, "manifest.json"))


def test_elastic_restore_resharded(tmp_path):
    """Save under one sharding, restore under another (mesh-agnostic)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = _state()
    mgr.save_sync(1, state)
    mesh = make_mesh((1,), ("data",))
    shardings = jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, P("data") if getattr(x, "ndim", 0) > 0
                                else P()), state)
    restored, _ = mgr.restore(1, jax.tree_util.tree_map(jnp.zeros_like, state),
                              shardings=shardings)
    assert tree_allclose(state, restored)
    leaf = restored["w"]
    assert leaf.sharding.spec == P("data")


def test_available_steps_skips_malformed_entries(tmp_path):
    """Stray step_* litter (editor backups, aborted copies, human notes)
    must not poison the directory scan with a ValueError."""
    mgr = CheckpointManager(str(tmp_path), keep=8)
    mgr.save_sync(5, _state())
    mgr.save_sync(12, _state())
    for junk in ("step_final", "step_12_copy", "step_", "step_abc"):
        d = os.path.join(str(tmp_path), junk)
        os.makedirs(d)
        with open(os.path.join(d, "manifest.json"), "w") as f:
            f.write("{}")
    # a plain *file* named step_<int> (no manifest inside) is skipped too
    with open(os.path.join(str(tmp_path), "step_99"), "w") as f:
        f.write("not a checkpoint")
    assert mgr.available_steps() == [5, 12]
    assert mgr.latest_step() == 12
    mgr.close()
