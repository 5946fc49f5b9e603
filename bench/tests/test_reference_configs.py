"""The reference and its control read nothing that the four-chip
configuration changes: for ex4_p8 and ex4_p4_4chip they give the same
numbers, so readings of the control on one stand for the other."""
import numpy as np
import pytest

from bench import control, harness

CONFIGS = ("ex4_p8", "ex4_p4_4chip")
SEED = 2**31 + 123


class Reads(dict):
    """A configuration that records the keys read from it."""

    def __init__(self, d):
        super().__init__(d)
        self.read = set()

    def __getitem__(self, key):
        self.read.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.read.add(key)
        return super().get(key, default)


def config(name: str) -> dict:
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}[name]
    return harness.load_json(harness.ROOT / entry["file"])


def outputs(cfg: dict, positions: list) -> dict:
    ref = harness.reference_module(cfg)
    ys = ref.observations(cfg, SEED, positions)
    xb = np.zeros((len(positions), int(cfg["n"])))
    out = {"ys": np.stack(ys),
           "solve": ref.solve(cfg, np.stack(positions), np.stack(ys), xb)}
    for operand in (None, control.CONTROL, "bfloat16"):
        out[f"chain.{operand}"] = ref.chain(cfg, positions, ys,
                                            operand=operand)
    return out


def test_the_configurations_name_one_reference():
    assert {config(c)["reference"] for c in CONFIGS} == {
        "bench/reference.py"}


def test_reference_and_control_give_the_same_output_for_both():
    rng = np.random.default_rng(SEED)
    m = int(config(CONFIGS[0])["m_obs"])
    positions = [rng.beta(2, 5, m) for _ in range(2)]
    runs = []
    for name in CONFIGS:
        cfg = Reads(config(name))
        runs.append((cfg, outputs(cfg, positions)))
    (a, out_a), (b, out_b) = runs
    assert a.read == b.read
    for key in a.read:
        assert a[key] == b[key], key
    # What the four-chip configuration changes is none of it.
    changed = {k for k in dict(a) if dict(a).get(k) != dict(b).get(k)}
    assert {"p", "overlap", "solver", "comm"} <= changed
    assert not changed & a.read
    for k in out_a:
        np.testing.assert_array_equal(out_a[k], out_b[k], err_msg=k)
    assert np.isfinite(out_a[f"chain.{control.CONTROL}"]).all()
    assert not np.array_equal(out_a[f"chain.{control.CONTROL}"],
                              out_a["chain.None"])


@pytest.mark.parametrize("name", CONFIGS)
def test_every_engine_key_of_the_configuration_is_an_engine_field(name):
    """The harness builds the engine from the file's EngineConfig keys;
    the four-chip file names the sharded path through them alone."""
    eng = harness.engine_config(config(name), seed=SEED)
    cfg = config(name)
    for key in ("n", "p", "overlap", "solver", "comm", "iters", "mu"):
        assert getattr(eng, key) == cfg[key], key
