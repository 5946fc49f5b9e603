"""The plain reference against a dense solve of the engine's own
operators, its observations against the engine's, its chain, and its
control one precision down."""
import numpy as np
import pytest

from bench import reference
from repro.core import cls

CFG = {"n": 192, "smooth": 0.25, "truth_drift": 0.05, "obs_noise": 1e-3}


def beta_positions(rng, C, m):
    return list(np.sort(rng.beta(2, 5, (C, m)), axis=1))


@pytest.mark.parametrize("dist", ["beta", "swarm"])
def test_reference_matches_dense_normal_equations(dist):
    n, m, C = CFG["n"], 240, 3
    rng = np.random.default_rng(3)
    if dist == "beta":
        pos = np.stack(beta_positions(rng, C, m))
    else:
        pos = np.sort(np.clip(0.05 + 0.08 * rng.normal(size=(C, m)), 0,
                              np.nextafter(1, 0)), axis=1)
    y1 = rng.normal(size=(C, m))
    xb = rng.normal(size=(C, n))
    x = reference.solve(CFG, pos, y1, xb)
    for c in range(C):
        H0 = cls.state_operator(n, 0.25)
        H1 = cls.observation_operator(n, pos[c])
        A = np.concatenate([H0, H1])
        b = np.concatenate([H0 @ xb[c], y1[c]])
        want = np.linalg.solve(A.T @ A, A.T @ b)
        assert np.abs(x[c] - want).max() <= 1e-12 * np.abs(want).max()


def test_observations_are_the_engines():
    """The reference draws the same truth walk and noise from the seed as
    the configuration says, without the program's help."""
    from repro.assim import AssimilationEngine, EngineConfig
    seed = 2**31 + 21
    eng = AssimilationEngine(EngineConfig(n=CFG["n"], p=4, seed=seed,
                                          solver_kernel="jnp"))
    pos = beta_positions(np.random.default_rng(5), 3, 150)
    got = [eng.prepare(c, p).y1 for c, p in enumerate(pos)]
    want = reference.observations(CFG, seed, pos)
    for g, w in zip(got, want):
        assert np.abs(g - w).max() <= 1e-12
    other = reference.observations(CFG, seed + 1, pos)
    assert np.abs(other[0] - want[0]).max() > 1e-3


def test_chain_carries_its_own_analyses():
    rng = np.random.default_rng(6)
    pos = beta_positions(rng, 4, 200)
    ys = reference.observations(CFG, 9, pos)
    xs = reference.chain(CFG, pos, ys)
    xb = np.zeros(CFG["n"])
    for c in range(4):
        xb = reference.solve(CFG, pos[c][None], ys[c][None], xb[None])[0]
        assert np.abs(xs[c] - xb).max() <= 1e-12 * np.abs(xb).max()


def test_control_reads_far_above_float32_rounding():
    n, m = 256, 300
    cfg = dict(CFG, n=n)
    rng = np.random.default_rng(4)
    pos = np.sort(rng.beta(2, 5, (2, m)), axis=1)
    y1, xb = rng.normal(size=(2, m)), rng.normal(size=(2, n))
    x = reference.solve(cfg, pos, y1, xb)
    gap8 = reference.relative_gap(
        reference.solve(cfg, pos, y1, xb, operand="float8_e4m3fn"), x)
    gap16 = reference.relative_gap(
        reference.solve(cfg, pos, y1, xb, operand="bfloat16"), x)
    assert (gap8 > 1e-2).all() and (gap16 > 1e-4).all()
    assert (gap8 > 5 * gap16).all()
