"""Logical-axis sharding rules (MaxText-style), resolved against a mesh.

Every parameter and activation is annotated with *logical* axis names; a
per-family rule table maps logical names to mesh axes. Rules silently drop
mesh axes that don't exist in the current mesh (so the same annotations work
on the single-pod ``("data","model")`` and multi-pod ``("pod","data","model")``
meshes, and on the 1-device CPU mesh used by smoke tests, where everything
resolves to replicated).

Parallelism encoding:

* ``batch``    → ("pod", "data")   — DP across pods and the data axis
* ``d_in``     → ("data",)         — FSDP: weights sharded on their input dim,
                                     all-gathered per layer inside the scan
* ``feat``/``heads_flat``/``vocab`` → ("model",)  — megatron TP
* ``act_seq``  → ("model",)        — sequence parallelism at layer boundaries
                                     (dense/MoE/enc-dec/VLM profile)
* ``kv_seq``   → ("model",)        — decode KV caches sharded along sequence,
                                     attention reduces with psum (works for any
                                     GQA head count — see DESIGN.md)
* ``experts``  → ("model",)        — expert parallelism (MoE)
* SSM profile: activations stay sequence-local; cell state dims shard on model.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Rules", "rules_for", "logical_to_spec", "shardings_for_tree", "constrain"]

Rules = Dict[str, Tuple[str, ...]]

_TP_RULES: Rules = {
    "batch": ("pod", "data"),
    "act_seq": ("model",),
    "kv_seq": ("data", "model"),  # decode caches; batch claims "data" first
    "d_in": ("data",),
    "feat": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "layers": (),
    "none": (),
}

_SSM_RULES: Rules = {
    # sequence stays local (the state recurrence is sequential in seq), so the
    # batch takes every available mesh axis (pure DP); weights stay FSDP+TP
    # sharded. When the batch doesn't cover the full mesh (decode shapes),
    # the divisibility-aware resolver falls back to a prefix of the axes and
    # frees "model" for the kv_seq / cell dims.
    # order matters: preferring (data, model) keeps B=1/device on BOTH
    # meshes at global_batch=256 (the multi-pod (pod,data) prefix gave
    # B=8/device and 75 GB temps); the pod axis joins only when the batch
    # covers it (global_batch ≥ 512 — the elastic-scaling recommendation
    # for SSM/hybrid training, DESIGN.md §4).
    "batch": ("data", "model", "pod"),
    "act_seq": (),
    "kv_seq": ("data", "model"),  # long_500k batch=1 frees both axes
    "d_in": ("data",),
    "feat": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "layers": (),
    "none": (),
}


def rules_for(family: str) -> Rules:
    return _SSM_RULES if family in ("ssm", "hybrid") else _TP_RULES


def logical_to_spec(
    logical: Tuple[Optional[str], ...], rules: Rules, mesh: Mesh,
    shape: Optional[Tuple[int, ...]] = None,
) -> P:
    """Map logical axis names (None = replicated) to a PartitionSpec.

    Resolution is left-to-right, divisibility-aware and duplicate-free:
    each dimension takes the longest *prefix* of its rule's mesh axes that
    (a) exists in the mesh, (b) hasn't been claimed by an earlier dimension
    of the same tensor, and (c) divides the dimension size (when ``shape``
    is provided). This is what lets one rule table serve every mesh and every
    (train/prefill/decode/long-context) shape — e.g. a decode batch of 128
    takes ("pod","data") and leaves "model" free for the kv_seq dim.
    """
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    used: set = set()
    out = []
    for i, ax in enumerate(logical):
        if ax is None:
            out.append(None)
            continue
        if ax not in rules:
            raise KeyError(f"unknown logical axis {ax!r}")
        candidates = [a for a in rules[ax] if a in names and a not in used]
        dim = shape[i] if shape is not None and i < len(shape) else None
        chosen: list = []
        prod = 1
        for a in candidates:
            if dim is not None and dim % (prod * sizes[a]) != 0:
                continue  # skip non-dividing axes but keep trying later ones
            prod *= sizes[a]
            chosen.append(a)
        if not chosen:
            out.append(None)
            continue
        used.update(chosen)
        out.append(tuple(chosen) if len(chosen) > 1 else chosen[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shardings_for_tree(
    logical_tree: Any, abstract_tree: Any, rules: Rules, mesh: Mesh
) -> Any:
    """NamedSharding tree for a pytree of logical-axis annotations."""

    def one(logical, leaf):
        spec = logical_to_spec(logical, rules, mesh, shape=tuple(leaf.shape))
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, logical_tree, abstract_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def constrain(x, rules: Rules, *logical: Optional[str]):
    """with_sharding_constraint via logical names (requires a mesh context).

    No-op outside jit on a single device (smoke tests).
    """
    mesh = _current_mesh()
    if mesh.empty or mesh.size == 1:
        return x
    spec = logical_to_spec(tuple(logical), rules, mesh, shape=tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh() -> Mesh:
    # The mesh entered with ``with mesh:`` (empty outside one). A private
    # JAX API: if a JAX upgrade moves it, this fails loudly rather than
    # turning every sharding constraint off.
    from jax._src import mesh as mesh_lib

    return mesh_lib.thread_resources.env.physical_mesh
