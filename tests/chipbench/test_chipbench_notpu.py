"""Without a TPU the benchmark exits non-zero and prints no result line,
and so it does in a directory that holds only the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny

REPO = chipbench_tiny.REPO
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = BENCH["command"] + ["--workload", BENCH["workloads"][0]["name"],
                              "--seed", "2147483713", "--seconds", "1",
                              "--trace", "0"]
    return subprocess.run([sys.executable] + cmd[1:], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            pytest.fail(f"printed a result line: {line}")


def test_no_tpu_fails_without_result():
    proc = _run(REPO)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_fail_without_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    _no_result(proc)
    assert "src/repro" in proc.stderr
