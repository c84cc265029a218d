"""Cells of the benchmark cut to sizes a CPU test run can hold: the same
drivers, references and limits, with fewer tasks or the smoke widths."""

import copy

from chipbench.files import load_benchmark, resolve_cell


def thermal_cell():
    cell = resolve_cell(load_benchmark(), "thermal.design")
    cfg = copy.deepcopy(cell.config)
    for item in cfg["tasks"] + cfg["packets"]:
        if "count" in item:
            item["count"] = max(2, item["count"] // 64)
    n = sum(t.get("count", 1) for t in cfg["tasks"])
    cnn = n - 6
    cfg["expect"].update(n_tasks=n, nnz_reads=1 + 2 * cnn + 2 + 1,
                         q_min=0.132, q_min_tolerance=0.01)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, q_points=15, sample_q_points=6)
    return cell


def serve_cell():
    """The program's smoke widths of qwen1.5-0.5b, which it runs with
    ``smoke=True``."""
    cell = resolve_cell(load_benchmark(), "qwen05.serve")
    cell.config = dict(cell.config, num_hidden_layers=2, hidden_size=64,
                       num_attention_heads=4, num_key_value_heads=4,
                       intermediate_size=160, vocab_size=256,
                       rope_theta=10000.0)
    cell.traffic = dict(
        cell.traffic, requests_per_schedule=8, compare=3,
        prompt={"median": 20, "sigma": 0.5, "edges": [16, 32]},
        output={"median": 5, "sigma": 0.5, "edges": [4, 8]})
    return cell
