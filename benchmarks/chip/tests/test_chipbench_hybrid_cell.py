"""The Zamba2 serving cell at the program's smoke widths on the CPU, with a
smoke cell of its own: a sound run is correct, and a run that serves
altered tokens is not, nor one that drops a commit. Its FLOP count against
a hand count."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import json
import time

import pytest

from chipbench import work_hybrid
from chipbench.files import BENCH_DIR, load_benchmark, resolve_cell
from chipbench.harness import run_cell

OPTIONS = {"smoke": True}


def hybrid_cell():
    """zamba2.serve at the program's smoke widths of zamba2-7b (6 layers,
    hybrid layers 2 and 4, two shared blocks, chunk 8), which it runs with
    ``smoke=True``."""
    cell = resolve_cell(load_benchmark(), "zamba2.serve")
    cell.config = dict(
        cell.config, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, attention_head_dim=32, attention_hidden_size=128,
        ffn_hidden_size=160, intermediate_size=160, vocab_size=256,
        mamba_d_state=16, mamba_headdim=16, n_mamba_heads=8, chunk_size=8,
        adapter_rank=8, num_hidden_layers=6, hybrid_layer_ids=[2, 4],
        layers_block_type=["mamba", "mamba", "hybrid", "mamba", "hybrid", "mamba"])
    cell.traffic = dict(
        cell.traffic, requests_per_schedule=8, compare=3,
        prompt={"median": 20, "sigma": 0.5, "edges": [13, 32]},
        output={"median": 5, "sigma": 0.5, "edges": [4, 8]})
    return cell


def run(capsys, **options):
    res = run_cell(hybrid_cell(), 2**31 + 7, 0.5, False, time.perf_counter(),
                   require_tpu=False, options=dict(OPTIONS, **options))
    capsys.readouterr()
    return res


def test_sound_run_is_correct(capsys):
    res = run(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "latency_p95_ms", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


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
    assert res["checks"]["served_logit_gap"]["value"] > \
        res["checks"]["served_logit_gap"]["limit"]


def test_dropped_commit_is_not_correct(capsys):
    res = run(capsys, fault="drop_commit")
    assert res["correct"] is False
    assert res["checks"]["cycles_extra"]["value"] > 0


def test_program_config_must_match_the_file():
    from chipbench.files import load_module

    cell = hybrid_cell()
    cell.config = dict(cell.config, mamba_ngroups=1)
    driver = load_module(cell.driver_path).Driver(cell, 1, None, OPTIONS)
    with pytest.raises(ValueError, match="ssm_ngroups"):
        driver._model_config(smoke=True)


PUBLISHED = json.load(open(BENCH_DIR / "configs" / "zamba2-7b.json"))


def test_flops_by_hand():
    d, d_in, F, H, q, ff = 3584, 7168, 7424, 112, 7168, 14336
    mixer = 2 * d * (d_in + F + H) + 2 * d_in * d + 2 * 4 * F + 5 * d_in * 64
    assert work_hybrid.mixer_flops(PUBLISHED) == mixer
    shared = 2 * (7168 * 3 * q + q * d + 3 * d * ff + 128 * (d + 2 * ff) + d * d)
    assert work_hybrid.shared_flops(PUBLISHED, 10) == shared + 4 * 10 * q
    assert work_hybrid.forward_flops(PUBLISHED, 3, 10, True) == \
        3 * (12 * mixer + 2 * (shared + 40 * q) + 2 * d * 32000)
    # prefill of 5 (context 3) with the last position's logits, then 1 step
    assert work_hybrid.request_flops(PUBLISHED, 1, 5, 2) == \
        5 * (12 * mixer + 2 * (shared + 12 * q)) + 2 * d * 32000 \
        + (12 * mixer + 2 * (shared + 24 * q) + 2 * d * 32000)
