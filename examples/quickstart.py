"""Quickstart: the paper's pipeline in ~40 lines.

Non-uniform observations -> DyDD load balancing -> DD-KF distributed solve,
validated against the sequential KF estimate (error_DD-DA ~ 1e-14).

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import cls, dd, ddkf, dydd, kalman  # noqa: E402
from repro.data import observations  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402


def main():
    use_compile_cache()
    n, m, p = 512, 1200, 8

    # 1. A CLS state-estimation problem with spatially clustered (sparse,
    #    non-uniform) observations — the setting DyDD exists for.
    obs = observations.make_observations(m, kind="clustered", seed=42)
    prob = cls.local_problem(jax.random.PRNGKey(0), n, obs)

    # 2. Static uniform DD would be badly unbalanced:
    static_counts = np.histogram(obs, bins=p, range=(0, 1))[0]
    print(f"static DD loads:   {static_counts}  "
          f"(E = {dydd.balance_ratio(static_counts):.3f})")

    # 3. DyDD: DD step + diffusion scheduling + boundary migration.
    res = dydd.dydd_1d(obs, p)
    print(f"after DyDD:        {res.loads_final}  "
          f"(E = {res.efficiency:.3f}, {res.rounds} scheduling rounds, "
          f"{res.total_movement} obs moved)")

    # 4. DD-KF: the distributed Kalman/CLS solve on the balanced DD.
    dec = dd.decompose_1d(n, res.boundaries)
    packed = ddkf.pack(prob, dec)
    x_ddkf = ddkf.solve_vmapped(packed, iters=120)

    # 5. Validate against the sequential KF (the paper's reference).
    x_kf = kalman.solve_cls_sequential(prob, block=50)
    err = float(jnp.linalg.norm(x_ddkf - x_kf))
    print(f"error_DD-DA = ||x_KF - x_DD-KF|| = {err:.2e}   "
          f"(paper reports ~1e-11 at n=2048)")
    assert err < 1e-8


if __name__ == "__main__":
    main()
