"""The entry refuses to measure anything but a TPU, and refuses to run
without the program beside it."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
ARGS = ["--workload", "chatglm3-6b.chat", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_naming_the_platform():
    p = _run(ROOT, "benchmarks/chip/bench.py")
    assert p.returncode == 2, p.stderr
    assert "cpu" in p.stderr and "TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "benchmarks/chip/bench.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_the_control_script_refuses_the_cpu_too():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/control.py",
                        "--workload", "chatglm3-6b.chat", "--seeds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2 and "cpu" in p.stderr
