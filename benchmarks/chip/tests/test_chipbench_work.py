"""Operations and bytes counted from shapes, against hand counts."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import json

import pytest

from chipbench import work
from chipbench.files import BENCH_DIR, load_module

ref = load_module(BENCH_DIR / "references" / "partition_dp.py")

TINY = {
    "packets": [{"name": "a", "bytes": 10}, {"name": "b", "bytes": 20},
                {"name": "out", "bytes": 4, "keep": True}],
    "tasks": [{"name": "t1", "cost": 1.0, "reads": [], "writes": ["a"]},
              {"name": "t2", "cost": 1.0, "reads": ["a"], "writes": ["b"]},
              {"name": "t3", "cost": 1.0, "reads": ["a", "b"], "writes": ["out"]}],
}


def test_column_ops_by_hand():
    g = ref.build(TINY)
    slots = ref.read_slots(g)
    # (j, lt, writer, linf): t2 reads a (last touched by 1, written by 1,
    # last used by 3); t3 reads a (last touched by 2) and b (2, 2, 3).
    assert slots == [(2, 1, 1, 3), (3, 2, 1, 3), (3, 2, 2, 3)]
    # extend: 0 + 1 + 2 = 3; loads: slot (2,1): 0, (3,2): 0, (3,2): 0;
    # freed stores at j=3: a (writer 1) 1 + b (writer 2) 2 = 3
    assert work.dp_column_ops(3, slots) == 6


def test_sweep_work_by_hand():
    ops, nbytes = work.dp_sweep_work(n=3, nnz_reads=3, column_ops=6, lanes=4,
                                     ops_per_candidate=3)
    assert ops == 6 + 6 * 4 * 3          # 6 candidate bursts <i, j>, i <= j
    assert nbytes == 4 * (4 + 6 + 15) + 2 * 4 * 3 * 4


def test_thermal_counts():
    cfg = json.load(open(BENCH_DIR / "configs" / "thermal-headcount.json"))
    g = ref.build(cfg)
    assert (g.n, g.nnz_reads) == (5458, 10908)
    col = work.dp_column_ops(g.n, ref.read_slots(g))
    ops, _ = work.dp_sweep_work(g.n, g.nnz_reads, col, 128, 3)
    candidates = 5458 * 5459 // 2
    assert ops == col + candidates * 128 * 3
    assert 5.7e9 < ops < 5.9e9


def test_lm_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
           "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 100}
    # per layer: 2 (8*8 + 2*8*4 + 8*8 + 3*8*16) = 1152; attention 4 * ctx * 8
    assert work.lm_forward_flops(cfg, 5, 10, False) == 5 * 3 * (1152 + 320)
    assert work.lm_forward_flops(cfg, 5, 10, True) == 5 * 3 * (1152 + 320) + 5 * 1600


def test_qwen_flops_per_token():
    cfg = json.load(open(BENCH_DIR / "configs" / "qwen1.5-0.5b.json"))
    per_token = work.lm_forward_flops(cfg, 1, 0, True)
    # 2 x (308 M layer weights + 156 M tied head), without the biases
    assert per_token == pytest.approx(2 * (24 * (4 * 1024**2 + 3 * 1024 * 2816)
                                           + 151936 * 1024))
