"""Sharding resolver invariants: dedupe, divisibility, greedy axis skipping."""

import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.models.sharding import logical_to_spec, rules_for

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestResolver:
    def test_dense_train_batch(self):
        r = rules_for("dense")
        spec = logical_to_spec(("batch", "act_seq", None), r, SINGLE,
                               shape=(256, 4096, 1024))
        assert spec == P(("data",), ("model",)) or spec == P("data", "model")

    def test_no_duplicate_axes_in_one_spec(self):
        r = rules_for("ssm")
        # batch wants (data, model, pod); kv_seq wants (data, model):
        # whatever batch takes, kv_seq must not reuse
        spec = logical_to_spec(("batch", "kv_seq"), r, SINGLE,
                               shape=(128, 32768))
        used = []
        for entry in spec:
            if entry is None:
                continue
            used.extend(entry if isinstance(entry, tuple) else (entry,))
        assert len(used) == len(set(used))

    def test_greedy_skips_non_dividing_axis(self):
        # batch=128 on multi-pod ssm rules: model (16·16=256) does not divide,
        # but pod (·2) after skipping model does → (data, pod)
        r = rules_for("ssm")
        spec = logical_to_spec(("batch",), r, MULTI, shape=(128,))
        axes = spec[0]
        axes = axes if isinstance(axes, tuple) else (axes,)
        assert "data" in axes and "pod" in axes and "model" not in axes

    def test_batch_one_replicated(self):
        r = rules_for("ssm")
        spec = logical_to_spec(("batch", "kv_seq"), r, MULTI,
                               shape=(1, 524288))
        assert spec[0] is None  # batch=1 cannot shard
        kv = spec[1] if len(spec) > 1 else None
        assert kv is not None  # kv_seq takes the freed axes

    def test_unknown_logical_raises(self):
        with pytest.raises(KeyError):
            logical_to_spec(("nope",), rules_for("dense"), SINGLE, shape=(8,))

    def test_smoke_mesh_all_replicated(self):
        tiny = AbstractMesh((1, 1), ("data", "model"))
        r = rules_for("dense")
        spec = logical_to_spec(("batch", "act_seq", None), r, tiny,
                               shape=(2, 32, 64))
        # 1-sized axes technically divide; spec may name them but they are
        # size-1 → effectively replicated. Just ensure it resolves.
        assert isinstance(spec, P)
