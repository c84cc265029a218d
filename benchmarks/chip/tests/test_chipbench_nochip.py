"""Off a TPU the command exits nonzero and prints no result; so it does in
a directory that holds only BENCHMARK.json and the benchmark's files."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.files import BENCH_DIR, ROOT, load_benchmark

CELLS = [w["name"] for w in load_benchmark()["workloads"]]


def run(cwd, cell, env):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", str(2**31 + 17), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=180)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


@pytest.mark.parametrize("cell", CELLS)
def test_no_tpu_no_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = run(ROOT, cell, env)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = run(tmp_path, CELLS[0], env)
    assert p.returncode != 0
    assert no_result(p.stdout)
