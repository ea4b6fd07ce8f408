"""The harness's arithmetic and its refusals (CPU, no accelerator)."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import load  # noqa: E402
from bench.harness import program_seed  # noqa: E402
from bench.load import Request  # noqa: E402


def test_samples_per_s_is_all_samples_over_all_the_window():
    reqs = [Request(due=0.0, asked=100, send=0.0, done=1.0),
            Request(due=0.5, asked=300, send=0.5, done=4.0),
            Request(due=0.2, asked=50, send=0.2, done=2.0, error="lost")]
    # 400 delivered samples; the window runs to the last answer at 4 s
    assert load.samples_per_s(reqs) == pytest.approx(100.0)
    assert load.window_s(reqs) == 4.0


def test_program_seed_takes_seeds_beyond_32_bits():
    a, b = program_seed(2 ** 33 + 5), program_seed(2 ** 33 + 6)
    assert 0 <= a < 2 ** 31 and a != b and a == program_seed(2 ** 33 + 5)


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "uq1_sf1.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
