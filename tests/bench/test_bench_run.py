"""``bench/run.py`` refuses to run without a TPU, and without the
program beside it, and prints no result either way."""
import json
import os
import shutil
import subprocess
import sys

from bench_tiny_root import REPO

CELL = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"][0]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    return not lines or not lines[-1].lstrip().startswith("{")


def test_exits_nonzero_without_a_tpu():
    p = _run(REPO, {})
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    p = _run(str(tmp_path), {})
    assert p.returncode != 0
    assert _no_result(p.stdout)
