"""Fault-tolerant checkpointing: atomicity, corruption detection, async,
elastic re-shard."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.checkpoint import manager as ckpt


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.normal(size=(4, 8))),
                   "b": jnp.asarray(rng.normal(size=(8,)))},
        "opt": {"m": jnp.zeros((4, 8)), "step": jnp.asarray(7)},
    }


def test_roundtrip(tmp_path):
    tree = _tree()
    path = ckpt.save_pytree(tree, str(tmp_path), step=3,
                            metadata={"loader": {"seed": 1, "step": 9}})
    got, manifest = ckpt.restore_pytree(path, like=tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert manifest["step"] == 3
    assert manifest["metadata"]["loader"]["step"] == 9


def test_verify_detects_corruption(tmp_path):
    tree = _tree()
    path = ckpt.save_pytree(tree, str(tmp_path), step=1)
    assert ckpt.verify(path)
    # corrupt one leaf file
    files = [f for f in os.listdir(path) if f.endswith(".npy")]
    victim = os.path.join(path, files[0])
    arr = np.load(victim)
    np.save(victim, arr + 1)
    assert not ckpt.verify(path)


def test_latest_skips_torn_checkpoint(tmp_path):
    tree = _tree()
    p1 = ckpt.save_pytree(tree, str(tmp_path), step=1)
    p2 = ckpt.save_pytree(tree, str(tmp_path), step=2)
    # tear the newest
    files = [f for f in os.listdir(p2) if f.endswith(".npy")]
    os.remove(os.path.join(p2, files[0]))
    assert ckpt.latest_checkpoint(str(tmp_path)) == p1


def test_tmp_dirs_ignored(tmp_path):
    tree = _tree()
    ckpt.save_pytree(tree, str(tmp_path), step=1)
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
    latest = ckpt.latest_checkpoint(str(tmp_path))
    assert latest.endswith("step_00000001")


def test_manager_async_and_gc(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    for s in range(5):
        mgr.save(tree, step=s, blocking=False)
    mgr.wait()
    kept = sorted(d for d in os.listdir(str(tmp_path))
                  if d.startswith("step_"))
    assert len(kept) == 2
    got = mgr.restore_latest(like=tree)
    assert got is not None
    mgr.close()


def test_restore_shape_mismatch_raises(tmp_path):
    tree = _tree()
    path = ckpt.save_pytree(tree, str(tmp_path), step=1)
    bad = {"params": {"w": jnp.zeros((3, 3)),
                      "b": jnp.zeros((8,))},
           "opt": {"m": jnp.zeros((4, 8)), "step": jnp.asarray(0)}}
    with pytest.raises(ValueError):
        ckpt.restore_pytree(path, like=bad)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_roundtrip_random_trees(tmp_path_factory, seed):
    tmp = tmp_path_factory.mktemp(f"ck{seed}")
    rng = np.random.default_rng(seed)
    tree = {"a": jnp.asarray(rng.normal(size=(rng.integers(1, 10),))),
            "nested": {"b": jnp.asarray(rng.integers(0, 5, size=(3, 2)))}}
    path = ckpt.save_pytree(tree, str(tmp), step=0)
    got, _ = ckpt.restore_pytree(path, like=tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture()
def fresh_meters():
    from repro.obs import meters as obs_meters
    prev = obs_meters.get_meters()
    m = obs_meters.Meters()
    obs_meters.set_meters(m)
    yield m
    obs_meters.set_meters(prev)


def test_latest_falls_back_on_truncated_leaf(tmp_path, fresh_meters):
    """A leaf .npy truncated mid-bytes (torn write surviving the rename)
    fails hash verification; latest_checkpoint falls back to the
    previous verified step and journals the skip."""
    from repro.runtime import chaos as chaos_mod
    tree = _tree()
    p1 = ckpt.save_pytree(tree, str(tmp_path), step=1)
    p2 = ckpt.save_pytree(tree, str(tmp_path), step=2)
    chaos_mod.tear_checkpoint(p2, seed=3)
    assert not ckpt.verify(p2)
    assert ckpt.latest_checkpoint(str(tmp_path)) == p1
    snap = fresh_meters.snapshot()
    assert snap["counters"]["checkpoint.corrupt_skipped"] == 1
    assert any(e["name"] == "checkpoint.corrupt_skipped"
               and e["path"] == p2 for e in snap["events"])


def test_latest_falls_back_on_corrupt_manifest(tmp_path):
    tree = _tree()
    p1 = ckpt.save_pytree(tree, str(tmp_path), step=1)
    p2 = ckpt.save_pytree(tree, str(tmp_path), step=2)
    from repro.runtime import chaos as chaos_mod
    chaos_mod.corrupt_manifest(p2, seed=7)
    assert ckpt.latest_checkpoint(str(tmp_path)) == p1


def test_gc_removes_stale_tmp_keeps_live(tmp_path, fresh_meters):
    """_gc sweeps staging dirs whose writer pid is dead, and leaves
    another live writer's staging dir alone."""
    import subprocess
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    # A certainly-dead writer pid: spawn-and-reap a child.
    child = subprocess.Popen(["true"])
    child.wait()
    dead = os.path.join(str(tmp_path),
                        f"step_00000005.{child.pid}-1.tmp")
    live = os.path.join(str(tmp_path),
                        f"step_00000006.{os.getpid()}-123.tmp")
    other = os.path.join(str(tmp_path), "step_00000007.tmp")  # no pid tag
    for d in (dead, live, other):
        os.makedirs(d)
    for s in range(3):
        mgr.save(tree, step=s, blocking=False)
    mgr.wait()
    assert not os.path.exists(dead)
    assert os.path.exists(live)
    assert os.path.exists(other)   # unparseable: never touched
    snap = fresh_meters.snapshot()
    assert snap["counters"]["checkpoint.stale_tmp_removed"] >= 1
    mgr.close()


def test_async_save_failure_emits_event(tmp_path, fresh_meters):
    """An async save that dies surfaces as an obs event/counter at
    failure time, and still raises on wait()."""
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
    # Squat a regular file on the checkpoint directory path so the
    # worker's makedirs fails — a realistic misconfigured-path failure.
    shutil.rmtree(str(tmp_path))
    with open(str(tmp_path), "w") as f:
        f.write("not a directory")
    mgr.save(_tree(), step=1, blocking=False)
    with pytest.raises(Exception):
        mgr.wait()
    snap = fresh_meters.snapshot()
    assert snap["counters"]["checkpoint.save_failed"] == 1
    assert any(e["name"] == "checkpoint.save_failed" and e["step"] == 1
               for e in snap["events"])
    mgr.close()
    os.remove(str(tmp_path))


@pytest.mark.slow
def test_elastic_remesh_subprocess(tmp_path):
    """Save under a (2,2) mesh, restore under (4,1) and (1,2) — the
    scale-up/down path (DESIGN.md §8)."""
    import subprocess
    import sys
    script = f"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import manager as ckpt
from jax.sharding import AxisType

def make_device_mesh(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(shape))

tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
mesh1 = make_device_mesh((2, 2), ("data", "model"))
sharded = jax.device_put(tree["w"], NamedSharding(mesh1, P("data", "model")))
ckpt.save_pytree({{"w": sharded}}, r"{tmp_path}", step=1)

for shape, axes, spec in [((4, 1), ("data", "model"), P("data", None)),
                          ((1, 2), ("data", "model"), P(None, "model"))]:
    mesh2 = make_device_mesh(shape, axes)
    like = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float64)}}
    shardings = {{"w": NamedSharding(mesh2, spec)}}
    got, _ = ckpt.restore_pytree(r"{tmp_path}", like=like,
                                 shardings=shardings)
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.arange(64.0).reshape(8, 8))
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([__import__("sys").executable, "-c", script],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
