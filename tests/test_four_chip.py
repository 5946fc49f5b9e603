"""Example 4's layout of one subdomain per device, at a small size, on a
forced four-of-eight-device CPU host (in a subprocess, so the main test
session keeps its single-device view): each device packs its own block,
the sharded solve is dispatched with no copy between devices, and the
analyses agree with the independent reference (``bench/reference.py``)
and with the single-device path."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.assim import AssimilationEngine, EngineConfig

ROOT = Path(__file__).resolve().parents[1]

# float32 rounding at this size: machine epsilon (1.19e-7) times the
# condition number of the CLS normal matrix N = H0^T H0 + H1^T H1, at
# most 5.5 on these streams, is 6.5e-7; the 60 sweeps' accumulated
# rounding gets 3x room over that.  (Measured: 2e-7 to 4.4e-7.)
F32_FLOOR = 16 * float(np.finfo(np.float32).eps)

SCRIPT = r"""
import importlib.util, json, sys
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.assim import AssimilationEngine, EngineConfig
from repro.core import dd, ddkf, dydd

REF, SEED = sys.argv[1], int(sys.argv[2])
spec = importlib.util.spec_from_file_location("bench_reference", REF)
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)
out = {}

# (a) the engine on the cell's path against the plain reference
n, m_obs, cycles = 128, 120, 6
cfg = EngineConfig(n=n, p=4, overlap=1, iters=60, solver="shardmap",
                   comm="neighbour", seed=SEED)
eng = AssimilationEngine(cfg)
xs = []
eng.on_analysis = lambda c, x: xs.append(np.asarray(x))
rng = np.random.default_rng(SEED)
positions = [rng.beta(2, 5, m_obs) for _ in range(cycles)]
journal = eng.run(positions)
config = {"n": n, "smooth": cfg.smooth, "obs_noise": cfg.obs_noise,
          "truth_drift": cfg.truth_drift}
ys = ref.observations(config, SEED, positions)
X = np.stack(xs).astype(np.float64)
xb = np.concatenate([np.zeros((1, n)), X[:-1]])
out["a"] = {
    "dtype": str(xs[0].dtype), "cycles": len(xs),
    "analysis_gap": ref.relative_gap(X, ref.solve(
        config, np.stack(positions), np.stack(ys), xb)).tolist(),
    "chain_gap": ref.relative_gap(X, ref.chain(config, positions,
                                               ys)).tolist()}

# (e) the engine's mesh and what its packing holds
prep = eng.prepare(cycles, rng.beta(2, 5, m_obs))
sub = NamedSharding(eng.mesh, P("sub"))
out["e"] = {
    "visible": len(jax.devices()),
    "mesh_devices": [d.id for d in eng.mesh.devices.flat],
    "first_devices": [d.id for d in jax.devices()[:4]],
    "A_loc_sharded": prep.packed_op.A_loc.sharding == sub,
    "steps": sorted(k for k in prep.phases if k.startswith("pack.")),
    "placed_bytes": [r.placed_bytes for r in journal.records],
    "H0_bytes": int(np.dtype(np.float32).itemsize * eng._H0.size),
    "H1_bytes": int(np.dtype(np.float32).itemsize * m_obs * n)}
try:
    AssimilationEngine(EngineConfig(n=n, p=16, solver="shardmap"))
    out["e"]["too_few"] = "built"
except ValueError as err:
    out["e"]["too_few"] = str(err)

# (b) the sharded packing against the single-device one
res = dydd.dydd_1d(positions[0], 4)
dec = dd.decompose_1d(n, res.boundaries, overlap=1)
A = rng.normal(size=(2 * n - 2 + m_obs, n)).astype(np.float32)
A[rng.random(A.shape) < 0.1] = -0.0
r = np.ones((A.shape[0],))
mesh = eng.mesh
one = ddkf.pack_operator(A, r, dec)
four = ddkf.pack_operator(A, r, dec, mesh=mesh)

def bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))

whole = NamedSharding(mesh, P())
out["b"] = {f: {"bits": bool(np.array_equal(bits(getattr(one, f)),
                                            bits(getattr(four, f)))),
                "sharded": getattr(four, f).sharding == sub,
                "whole": getattr(four, f).sharding == whole}
            for f in ("A_loc", "L_loc", "cols", "mask", "muov", "wdiv",
                      "mult", "mult_loc", "scatter_cols", "gather_cols",
                      "r", "b")}

# (c) dispatch under the guard; the single-device packing trips it
b = rng.normal(size=(A.shape[0],))
pk1, pk4 = ddkf.with_rhs(one, b), ddkf.with_rhs(four, b)
out["b"]["b_rhs"] = {"sharded": False, "whole": pk4.b.sharding == whole,
                     "bits": bool(np.array_equal(bits(pk1.b),
                                                 bits(pk4.b)))}
halo = dec.halo_exchange
xs4 = {}
with jax.transfer_guard_device_to_device("disallow"):
    for comm in ("neighbour", "allreduce"):
        xs4[comm] = np.asarray(jax.block_until_ready(ddkf.solve_shardmap(
            pk4, mesh, iters=60, comm=comm, halo=halo)))
try:
    with jax.transfer_guard_device_to_device("disallow"):
        jax.block_until_ready(ddkf.solve_shardmap(
            pk1, mesh, iters=60, comm="neighbour", halo=halo))
    out["c"] = {"unplaced": "dispatched"}
except Exception as err:
    out["c"] = {"unplaced": str(err)[:200]}
out["c"]["placed"] = sorted(xs4)

# (d) the sharded solve against the single-device one
xv = np.asarray(ddkf.solve_vmapped(pk1, iters=60))
out["d"] = {comm: float(np.abs(x - xv).max()) for comm, x in xs4.items()}
out["d"]["scale"] = float(np.abs(xv).max())
print("RESULT " + json.dumps(out))
"""

SEED = 2**31 + 91


@pytest.fixture(scope="module")
def four_chip():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT,
                          str(ROOT / "bench" / "reference.py"), str(SEED)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")]
    assert line, out.stdout[-2000:]
    return json.loads(line[-1][len("RESULT "):])


def test_engine_on_four_devices_agrees_with_the_reference(four_chip):
    a = four_chip["a"]
    assert a["dtype"] == "float32" and a["cycles"] == 6
    assert max(a["analysis_gap"]) <= F32_FLOOR, a
    assert max(a["chain_gap"]) <= F32_FLOOR, a


def test_sharded_packing_is_the_single_device_packing_laid_out(four_chip):
    """Bit for bit, each field with the sharding the solve's in_specs
    name: the (p, ...) fields over "sub", r, b and mult whole."""
    b = four_chip["b"]
    whole = {"r", "b", "mult", "b_rhs"}
    for field, got in b.items():
        assert got["bits"], field
        assert got["whole" if field in whole else "sharded"], field


def test_sharded_solve_dispatches_with_no_copy_between_devices(four_chip):
    c = four_chip["c"]
    assert c["placed"] == ["allreduce", "neighbour"]
    # The guard does see a packing left on one device.
    assert "Disallowed device-to-device transfer" in c["unplaced"]


def test_sharded_solve_matches_the_single_device_solve(four_chip):
    d = four_chip["d"]
    assert d["scale"] > 0.1
    assert d["neighbour"] <= 1e-5 and d["allreduce"] <= 1e-5, d


def test_engine_builds_its_mesh_from_the_first_p_devices(four_chip):
    e = four_chip["e"]
    assert e["visible"] == 8
    assert e["mesh_devices"] == e["first_devices"] == [0, 1, 2, 3]
    assert e["A_loc_sharded"]
    assert "pack.place" in e["steps"] and "pack.roundtrip" not in e["steps"]
    # H0 goes to the four devices once, H1 to each of them every cycle.
    placed = e["placed_bytes"]
    assert placed[0] == 4 * (e["H0_bytes"] + e["H1_bytes"])
    assert placed[1:] == [4 * e["H1_bytes"]] * (len(placed) - 1)
    assert "requires a mesh" in e["too_few"]


def test_single_device_engine_counts_the_bytes_it_places():
    """On one device the counter reads H0 and H1 on the first cycle and
    H1 alone after it, in the device's dtype; the copy is pack.roundtrip."""
    n, m_obs = 64, 90
    eng = AssimilationEngine(EngineConfig(n=n, p=2, iters=20))
    rng = np.random.default_rng(3)
    journal = eng.run([rng.beta(2, 5, m_obs) for _ in range(3)])
    itemsize = np.dtype(eng._H0_dev.dtype).itemsize
    h0, h1 = itemsize * eng._H0.size, itemsize * m_obs * n
    assert [r.placed_bytes for r in journal.records] == [h0 + h1, h1, h1]
    assert all("pack.roundtrip" in r.phases and "pack.place" not in r.phases
               for r in journal.records)
    assert "placed_bytes" in journal.records[0].to_dict()
