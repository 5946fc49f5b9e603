"""The entry point and the files it reads, without a chip."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ex4_p8.beta_network",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v0 imaginary")


def test_benchmark_file_names_only_what_exists():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"] for c in spec["configs"]}
    cells = {c["name"] for c in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and (harness.ROOT / c["file"]).exists()
        config = harness.load_json(harness.ROOT / c["file"])
        assert (harness.ROOT / config["reference"]).exists()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        mix = harness.load_json(harness.BENCH / "traffic"
                                / f"{w['traffic']}.json")
        assert (harness.BENCH / "generators"
                / f"{mix['generator']}.py").exists()
        assert (harness.BENCH / "limits" / f"{w['name']}.json").exists()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
