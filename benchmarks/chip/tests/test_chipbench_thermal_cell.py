"""The design-query cell at a small size on the CPU (interpret-mode
kernel): a sound run is correct; a run whose kernel answers are altered,
and the bfloat16 control, are not."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import time

import numpy as np

from chipbench.files import load_module
from chipbench.harness import compare, run_cell
from small_cells import thermal_cell

OPTIONS = {"interpret": True}


def run(capsys):
    res = run_cell(thermal_cell(), 2**31 + 12345, 0.5, False,
                   time.perf_counter(), require_tpu=False, options=OPTIONS)
    capsys.readouterr()
    return res


def test_sound_run_is_correct(capsys):
    res = run(capsys)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["metrics"]["solve_ms"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_altered_answer_is_not_correct(capsys, monkeypatch):
    from repro.kernels.partition_sweep import ops

    real = ops.sweep_columns

    def altered(*a, **kw):
        mns, bests = real(*a, **kw)
        if kw.get("objective", "sum") == "sum":
            mns = np.where(np.isfinite(mns), mns * 1.1, mns)
        return mns, bests

    monkeypatch.setattr(ops, "sweep_columns", altered)
    res = run(capsys)
    assert res["correct"] is False
    assert res["checks"]["e_total_rel_err"]["value"] > res["checks"]["e_total_rel_err"]["limit"]


def test_bfloat16_control_fails_a_limit():
    cell = thermal_cell()
    ref = load_module(cell.reference_path)
    d = load_module(cell.driver_path).Driver(cell, 7, ref, OPTIONS)
    d.setup()
    for seed in (2001, 2002, 2003):
        d.reseed(seed)
        d.run_unit()
        d.run_unit()
        checks = compare(cell, d.numbers(control=True))
        assert not all(c.ok for c in checks), [(c.name, c.value) for c in checks]
        assert all(c.ok for c in compare(cell, d.numbers()))
