"""The observation stream of a cell, from its traffic mix.

A mix (``bench/traffic/<name>.json``) is data: it names its generator,
``bench/generators/<generator>.py``, whose ``positions(mix, m, cycle,
rng)`` draws one cycle's observation positions from the mix's
parameters.  The mix's first ``setup_cycles`` cycles are the set-up's
warm-up: they are drawn from the mix's own ``setup_seed``, so every
run's set-up does the same work and finds its programs in the compile
cache.  The window's cycles continue the cycle count and are drawn from
the run's seed.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

GENERATORS = Path(__file__).resolve().parent / "generators"


def generator(mix: dict):
    """The ``positions`` function of the generator the mix names."""
    name = mix["generator"]
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{name}", GENERATORS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.positions


def setup_cycles(mix: dict, m: int) -> list:
    """The warm-up cycles (0 .. setup_cycles-1), from the mix's seed."""
    draw = generator(mix)
    rng = np.random.default_rng(int(mix["setup_seed"]))
    return [draw(mix, m, c, rng) for c in range(int(mix["setup_cycles"]))]


def window_cycles(mix: dict, m: int, seed: int):
    """Endless window cycles, numbered on from the warm-up, from the
    run's seed."""
    draw = generator(mix)
    rng = np.random.default_rng(seed)
    c = int(mix["setup_cycles"])
    while True:
        yield draw(mix, m, c, rng)
        c += 1
