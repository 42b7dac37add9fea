"""The run's refusals: no TPU, no program, no such cell."""
import os
import shutil
import subprocess
import sys

from bench import harness, manifest

ROOT = manifest.ROOT


def _run(cwd, *args, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT, "--workload", "ct-same-variance", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_an_unknown_cell():
    p = _run(ROOT, "--workload", "no-such-cell", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "ct-curvature", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


class _Dev:
    def __init__(self, platform, kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


def test_chip_problem_names_each_refusal():
    peaks = harness.load_peaks()
    assert harness.chip_problem([_Dev("cpu")], 1, peaks)
    assert harness.chip_problem([_Dev("tpu")], 4, peaks)
    assert harness.chip_problem([_Dev("tpu", "TPU v9")], 1, peaks)
    assert harness.chip_problem([_Dev("tpu")] * 4, 4, peaks) is None


def test_peaks_table_holds_v5e():
    v5e = harness.load_peaks()["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
