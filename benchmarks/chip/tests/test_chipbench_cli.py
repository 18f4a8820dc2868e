"""The command refuses to run without a TPU, and in a directory that
holds only BENCHMARK.json and the benchmark's files: non-zero exit and
nothing on standard output."""
import os
import shutil
import subprocess
import sys

import _chipbench

ARGS = ["--workload", "qwen3-g22", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "bench.py")]
        + ARGS, cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_the_cpu():
    p = _run(_chipbench.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_bare_directory(tmp_path):
    shutil.copy(os.path.join(_chipbench.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_chipbench.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    # past the look for a chip, the runner finds no program to run
    probe = ("import sys; sys.path.insert(0, 'benchmarks/chip'); "
             "import bench, spec; "
             "cell = spec.resolve(spec.load_benchmark(), 'qwen3-g22'); "
             "bench.make_runner(cell, 1, []).setup()")
    p = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
