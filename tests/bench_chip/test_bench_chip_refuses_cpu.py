"""Without a TPU the benchmark exits nonzero and prints no result; in a
directory holding only BENCHMARK.json and the benchmark's own files it
does the same."""
import os
import shutil
import subprocess
import sys

import bench

ARGS = ["--workload", "qwen2-1.5b-l4.t4096", "--seed", "3000000017",
        "--seconds", "1", "--trace", "0"]


def run_in(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=root,
        env=env, capture_output=True, text=True, timeout=300)


def assert_refused(proc):
    assert proc.returncode != 0, proc.stdout
    assert not [l for l in proc.stdout.splitlines()
                if l.strip().startswith("{")], proc.stdout


def test_the_harness_refuses_a_cpu_backend():
    proc = run_in(bench.CHECKOUT)
    assert_refused(proc)
    assert proc.returncode == 3
    assert "needs a TPU" in proc.stderr


def test_only_the_benchmark_files_is_not_enough(tmp_path):
    spec = bench.load_json(bench.CHECKOUT / "BENCHMARK.json")
    shutil.copy(bench.CHECKOUT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(bench.CHECKOUT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    assert_refused(run_in(tmp_path))
