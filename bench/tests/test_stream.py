import json

import numpy as np
import pytest

from bench import stream
from bench.generators import mixture
from bench.harness import BENCH


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


SWARM = {"generator": "mixture", "components": [{"weight": 1.0, "dist": "normal", "sigma": 0.08,
                         "centre": {"start": 0.15, "step": 0.05,
                                    "lo": 0.15, "hi": 0.85}}],
         "setup_cycles": 1, "setup_seed": 5}
MIXTURE = {"generator": "mixture", "components": [{"weight": 3.0, "dist": "beta", "a": 2, "b": 5},
                          {"weight": 1.0, "dist": "uniform"}],
           "setup_cycles": 1, "setup_seed": 6}


@pytest.mark.parametrize("m", [mix("beta_network"), SWARM, MIXTURE])
def test_seeded_sorted_in_unit_interval(m):
    a = [next(stream.window_cycles(m, 500, 2**31 + 9)) for _ in range(2)]
    b = next(stream.window_cycles(m, 500, 2**31 + 10))
    assert np.array_equal(a[0], a[1]) and not np.array_equal(a[0], b)
    for obs in a + stream.setup_cycles(m, 500):
        assert obs.shape == (500,) and (np.diff(obs) >= 0).all()
        assert obs.min() >= 0.0 and obs.max() < 1.0


def test_setup_cycles_do_not_depend_on_the_seed():
    m = mix("beta_network")
    s = stream.setup_cycles(m, 300)
    assert len(s) == m["setup_cycles"]
    assert all(np.array_equal(x, y)
               for x, y in zip(s, stream.setup_cycles(m, 300)))


def test_swarm_centre_reflects_within_its_range():
    c = SWARM["components"][0]["centre"]
    path = [mixture.reflected(c, k) for k in range(60)]
    assert path[0] == pytest.approx(0.15) and path[1] == pytest.approx(0.2)
    assert path[14] == pytest.approx(0.85) and path[15] == pytest.approx(0.8)
    assert min(path) >= 0.15 - 1e-12 and max(path) <= 0.85 + 1e-12
