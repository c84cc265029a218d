"""The serving cell at the program's smoke widths on the CPU: a sound run
is correct, and a run whose decode step serves altered tokens is not, nor
one with a fault planted under the timed path (a commit dropped, the steps
traced again inside the window)."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import time

import pytest

from chipbench.harness import run_cell
from small_cells import serve_cell

OPTIONS = {"smoke": True}


def run(capsys, **options):
    res = run_cell(serve_cell(), 2**31 + 99, 0.5, False, time.perf_counter(),
                   require_tpu=False, options=dict(OPTIONS, **options))
    capsys.readouterr()
    return res


def test_sound_run_is_correct(capsys):
    res = run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "latency_p95_ms", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_altered_token_is_not_correct(capsys, monkeypatch):
    import repro.launch.serve as serve

    real = serve._step_fns

    def altered(*a, **kw):
        prefill, decode = real(*a, **kw)

        def bad_decode(params, cache, tok, pos):
            logits, cache = decode(params, cache, tok, pos)
            return -logits, cache  # every decoded token becomes the worst

        return prefill, bad_decode

    monkeypatch.setattr(serve, "_step_fns", altered)
    res = run(capsys)
    assert res["correct"] is False


@pytest.mark.parametrize("fault, number", [("drop_commit", "cycles_extra"),
                                           ("drop_commit", "cycle_budget_excess"),
                                           ("drop_commit", "ledger_error"),
                                           ("retrace", "window_retraces")])
def test_planted_fault_is_not_correct(capsys, fault, number):
    res = run(capsys, fault=fault)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]
