"""End-to-end behaviour tests: the paper pipeline (DyDD -> DD-KF) at the
paper's configuration scale, and LM training with DyDD-balanced data +
checkpoint/restart equivalence (fault-tolerance path)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import manager as ckpt
from repro.core import cls, dd, ddkf, dydd
from repro.data import pipeline, observations
from repro.models import transformer
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import steps as steps_mod


@pytest.mark.slow
def test_paper_pipeline_end_to_end():
    """Paper §6 structure at reduced n: non-uniform observations, DyDD to
    balance (E -> 1), DD-KF solve, error_DD-DA at machine precision."""
    n, m, p = 256, 600, 8
    obs = observations.make_observations(m, kind="beta", seed=0)
    prob = cls.local_problem(jax.random.PRNGKey(0), n, obs)

    res = dydd.dydd_1d(obs, p)
    assert res.efficiency > 0.9
    assert res.loads_final.sum() == m

    dec = dd.decompose_1d(n, res.boundaries)
    packed = ddkf.pack(prob, dec)
    x_dd = ddkf.solve_vmapped(packed, iters=150)
    x_kf = cls.solve(prob)
    err = float(jnp.linalg.norm(x_dd - x_kf))
    assert err < 1e-8, err   # paper reports ~1e-11 at n=2048


@pytest.mark.slow
def test_train_checkpoint_restart_equivalence(tmp_path):
    """Train k steps, checkpoint, keep training; separately restore and
    retrain — identical losses (deterministic restart, DESIGN.md §8)."""
    cfg = configs.get_smoke_config("gemma3_1b")
    opt_cfg = AdamWConfig(lr=1e-3)
    step = steps_mod.make_train_step(cfg, opt_cfg, donate=False)

    loader = pipeline.BalancedLoader(vocab_size=cfg.vocab_size, dp=2,
                                     batch_per_shard=2, seq=32, seed=5)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)

    def batches(ld, k):
        out = []
        for _ in range(k):
            t, l, m = ld.next_batch()
            out.append({"tokens": jnp.asarray(t),
                        "labels": jnp.asarray(l),
                        "mask": jnp.asarray(m)})
        return out

    # steps 0-2
    for b in batches(loader, 3):
        loss, params, opt = step(params, opt, b)
    ckpt.save_pytree({"params": params, "opt": opt}, str(tmp_path), step=3,
                     metadata={"loader": loader.state_dict()})

    # continue 2 more steps -> reference losses
    ref_losses = []
    for b in batches(loader, 2):
        loss, params, opt = step(params, opt, b)
        ref_losses.append(float(loss))

    # restart from the checkpoint
    like = {"params": transformer.param_shapes(cfg, dtype=jnp.float32),
            "opt": {"m": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                transformer.param_shapes(cfg, dtype=jnp.float32)),
                "v": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                transformer.param_shapes(cfg, dtype=jnp.float32)),
                "step": jax.ShapeDtypeStruct((), jnp.int32)}}
    tree, manifest = ckpt.restore_pytree(str(tmp_path), like=like)
    loader2 = pipeline.BalancedLoader(vocab_size=cfg.vocab_size, dp=2,
                                      batch_per_shard=2, seq=32, seed=5)
    loader2.load_state_dict(manifest["metadata"]["loader"])
    p2, o2 = tree["params"], tree["opt"]
    got_losses = []
    for b in batches(loader2, 2):
        loss, p2, o2 = step(p2, o2, b)
        got_losses.append(float(loss))
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-6)


def test_crash_recovery_resumes_from_valid(tmp_path):
    """Simulated crash mid-write: restart ignores the torn checkpoint and
    resumes from the last verified one."""
    tree = {"w": jnp.arange(10.0)}
    ckpt.save_pytree(tree, str(tmp_path), step=1)
    p2 = ckpt.save_pytree({"w": jnp.arange(10.0) * 2}, str(tmp_path),
                          step=2)
    # "crash": corrupt newest manifest
    with open(os.path.join(p2, "manifest.json"), "w") as f:
        f.write("{not json")
    latest = ckpt.latest_checkpoint(str(tmp_path))
    assert latest.endswith("step_00000001")
    got, _ = ckpt.restore_pytree(latest, like=tree)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(10.0))


def test_dryrun_cell_helpers_importable():
    """The dry-run module guards: mesh factory is a function; shapes
    registry covers the 40 cells."""
    from repro.configs import shapes
    assert len(shapes.SHAPES) == 4
    assert len(configs.ARCHS) == 10
    n_run, n_skip = 0, 0
    for arch in configs.ARCHS:
        cfg = configs.get_config(arch)
        for s in shapes.SHAPES:
            ok, _ = shapes.cell_supported(cfg, s)
            n_run += ok
            n_skip += (not ok)
    assert n_run + n_skip == 40
    assert n_skip == 6   # long_500k skipped for 6 quadratic-cache archs
    # input_specs allocate nothing and are complete
    cfg = configs.get_config("whisper-large-v3")
    spec = shapes.input_specs(cfg, "train_4k")
    assert set(spec) == {"tokens", "labels", "mask", "frames"}
    assert all(isinstance(s, jax.ShapeDtypeStruct) for s in spec.values())


def test_chip_smoke_refuses_a_host_without_tpu():
    """chip_smoke.py never reports a CPU run as a chip run: with no TPU it
    exits non-zero, names the platform it found, and prints no ok line."""
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
