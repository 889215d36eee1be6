"""Every entry of BENCHMARK.json is found through the harness's lookup
by name alone, and the harness refuses a device it cannot measure."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from bench import spec, traffic

ROOT = Path(__file__).parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = spec.load_cell(cell, SPEC)
    names = [m.name for m in c.metrics]
    assert "setup_s" in names
    assert any(m.end_to_end and m.name != "setup_s" for m in c.metrics)
    assert any(not m.end_to_end for m in c.metrics)
    for m in c.metrics:
        assert callable(m.read)
        if not m.end_to_end:
            assert m.moves in names     # it moves a metric reported here
    assert traffic.prompt_pool(c.traffic["prompt"])
    assert c.limits["max_logit_gap"] > 0
    spec.load_reference(c.config["reference"])


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(spec.load_reader(m["name"]))


def test_every_config_file_is_under_the_benchmark_paths():
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.is_file()
        assert path.parts[len(ROOT.parts)] in SPEC["paths"]
        data = json.loads(path.read_text())
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in data["config"] and key in data["published"]


def test_an_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", SPEC)


def dev(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_peaks_of_a_known_tpu():
    peaks = spec.check_devices([dev()], 1)
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError, match="not in peaks.json"):
        spec.check_devices([dev(kind="TPU v9 imaginary")], 1)


def test_a_non_tpu_platform_is_an_error():
    with pytest.raises(spec.SpecError, match="needs a TPU"):
        spec.check_devices([dev(platform="cpu", kind="cpu")], 1)


def test_too_few_chips_is_an_error():
    with pytest.raises(spec.SpecError, match="4 chip"):
        spec.check_devices([dev()], 4)


def run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "bench" / "run.py"),
         "--workload", "minicpm-2b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_without_the_system_under_test_exits_nonzero(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
