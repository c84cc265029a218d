"""Every cell resolves to its files, BENCHMARK.json keeps the contract's
shape, and a new cell is added by files and entries alone."""

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
import re
import shutil
import subprocess
import sys

import pytest

from chipbench.files import BENCH_DIR, ROOT, load_benchmark, resolve_cell

BENCH = load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", [])) <= set(CELLS)


def test_free_text_fits_one_line():
    texts = [w["why"] for w in BENCH["workloads"]] + [c["why"] for c in BENCH["configs"]]
    texts += [c["source"] for c in BENCH["configs"]] + [m["layer"] for m in BENCH["per_layer"]]
    texts += BENCH["command"]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t for t in texts)
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = resolve_cell(BENCH, cell)
    assert c.driver_path.is_file() and c.reference_path.is_file()
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert c.metric_path(m["name"]).is_file(), m["name"]
    assert c.config["limits"]
    entry = c.config_entry
    assert entry["file"].startswith("benchmarks/chip/") and NAME.match(entry["name"])
    assert (ROOT / entry["file"]).is_file()


def test_per_layer_metric_must_name_its_cells():
    bench = json.loads(json.dumps(BENCH))
    del bench["per_layer"][0]["workloads"]
    with pytest.raises(ValueError, match="names no workloads"):
        resolve_cell(bench, CELLS[0])


def test_config_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


DUMMY_DRIVER = '''
class Driver:
    def __init__(self, cell, seed, ref, options):
        self.seed, self.ref = seed, ref
    def setup(self):
        self.n = 0
    def run_unit(self):
        self.n += 1
        return {"items": self.ref.answer(self.n)}
    @staticmethod
    def attempted_failed(units):
        return len(units), 0
    def release(self):
        pass
    def numbers(self, control=False):
        return {"wrong_items": float(self.n * (self.n + 1) // 2 - self.ref.total(self.n))}
'''
DUMMY_REF = '''
def answer(n):
    return n
def total(n):
    return sum(range(1, n + 1))
'''
DUMMY_METRIC = '''
def read(ctx):
    n = sum(u["items"] for u in ctx.units)
    return n / ctx.window_s if n else None
'''


def test_a_new_cell_is_files_and_entries(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, a driver, a
    reference and a metric as new files plus entries, and run the new cell
    through the unchanged harness."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dummy", "source": "https://example.org/dummy",
                             "file": "benchmarks/chip/configs/dummy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy.count", "config": "dummy",
                               "traffic": "dummy_count", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "items_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": ["dummy.count"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b = root / "benchmarks" / "chip"
    (b / "configs" / "dummy.json").write_text(json.dumps(
        {"reference": "dummy_ref", "limits": {"wrong_items": 0}}))
    (b / "traffic" / "dummy_count.json").write_text(json.dumps({"driver": "dummy_driver"}))
    (b / "drivers" / "dummy_driver.py").write_text(DUMMY_DRIVER)
    (b / "references" / "dummy_ref.py").write_text(DUMMY_REF)
    (b / "metrics" / "items_per_s.py").write_text(DUMMY_METRIC)
    script = (
        "import sys, time\n"
        f"sys.path.insert(0, {str(b)!r})\n"
        "from chipbench.files import load_benchmark, resolve_cell\n"
        "from chipbench.harness import run_cell\n"
        "run_cell(resolve_cell(load_benchmark(), 'dummy.count'), 3, 0.2, False,"
        " time.perf_counter(), require_tpu=False)\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"items_per_s", "setup_s"}
    assert res["checks"] == {"wrong_items": {"value": 0.0, "limit": 0.0}}
