"""Mixture generator: observation positions on [0, 1) drawn from a
mixture of distributions.

A mix that names ``"generator": "mixture"`` lists ``components``, each
with a ``weight`` and one of

* ``{"dist": "beta", "a": ..., "b": ...}``
* ``{"dist": "uniform"}``
* ``{"dist": "normal", "sigma": ..., "centre": {"start", "step", "lo",
  "hi"}}`` — a centre that moves ``step`` per cycle and is reflected
  within ``[lo, hi]`` (a triangle wave), so the stream has no end.

Each cycle draws ``m`` positions, clips them into [0, 1) and sorts them,
as ``repro.assim.streams`` does.  Copies of the Beta(2, 5) draw of
``repro.data.observations.make_observations`` and of the swarm of
``repro.assim.streams.drifting_swarm``.
"""
from __future__ import annotations

import numpy as np

_TOP = np.nextafter(1.0, 0.0)


def reflected(centre: dict, cycle: int) -> float:
    """Centre of a moving component at ``cycle``: start + step * cycle,
    reflected into [lo, hi]."""
    lo, hi = float(centre["lo"]), float(centre["hi"])
    span = hi - lo
    t = float(centre["start"]) - lo + float(centre["step"]) * cycle
    if span <= 0:
        return lo
    t = t % (2 * span)
    return lo + (t if t <= span else 2 * span - t)


def _draw(comp: dict, k: int, cycle: int,
          rng: np.random.Generator) -> np.ndarray:
    dist = comp["dist"]
    if dist == "beta":
        return rng.beta(float(comp["a"]), float(comp["b"]), k)
    if dist == "uniform":
        return rng.uniform(0.0, 1.0, k)
    if dist == "normal":
        c = reflected(comp["centre"], cycle)
        return c + float(comp["sigma"]) * rng.normal(size=k)
    raise ValueError(f"unknown traffic component {dist!r}")


def positions(mix: dict, m: int, cycle: int,
              rng: np.random.Generator) -> np.ndarray:
    """One cycle's ``m`` sorted observation positions in [0, 1)."""
    comps = mix["components"]
    w = np.asarray([float(c["weight"]) for c in comps])
    counts = rng.multinomial(m, w / w.sum()) if len(comps) > 1 else [m]
    obs = np.concatenate([_draw(c, int(k), cycle, rng)
                          for c, k in zip(comps, counts)])
    return np.sort(np.clip(obs, 0.0, _TOP))
