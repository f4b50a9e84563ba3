"""``benchmarks/run.py`` prints a result only where it may."""

import os
import shutil
import subprocess
import sys

from benchmarks.manifest import ROOT

ARGS = ["--workload", "falcon7b-train-1chip", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0"]


def run(cwd, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *ARGS, *extra], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def no_result(proc):
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    no_result(run(ROOT))


def test_without_the_program_it_exits_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    no_result(run(tmp_path))
    no_result(run(tmp_path, ["--cpu-rehearsal"]))
