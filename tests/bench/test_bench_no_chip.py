"""The benchmark's command fails, prints no result and does not fall back
to the CPU where JAX finds no accelerator, and fails where the program is
not beside it."""

import os
import shutil
import subprocess
import sys

import tinycell

CMD = ["bench/run.py", "--workload", "qwen1.5-4b.decode", "--seed", "3", "--seconds", "1",
       "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, *CMD], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_fails_without_an_accelerator():
    p = _run(tinycell.REPO)
    _no_result(p)
    assert "accelerator" in p.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(tinycell.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tinycell.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
