"""``lax.scan`` placement backend: the whole bandwidth × memory × Q grid
in one jitted call.

The batched re-expression of :func:`repro.core.placement.solve_placement_numpy`:

* the per-node burst DP (``S[i,b]`` over all span starts at once) becomes a
  ``lax.scan`` over the column index ``b``, carrying the full ``S`` table and
  emitting the parent column — ``vmap``-ed across every (node, q_scale) pair;
* the chain DP over node count becomes a ``lax.scan`` over ``k`` carrying
  ``dp_prev`` — ``vmap``-ed across every (link, memory, q) grid point, with
  the per-lane gathers (``S_all[:, z]``, ``memok[:, m]``, ``hop[l]``) inside
  the jit.

Bit-identity contract: this backend consumes the exact
:class:`~repro.core.placement.PlacementInputs` arrays the numpy solver does
and performs the same float64 operations in the same order (masked
candidates via the shared first-min idiom, the ``(dp + hop) + seg``
accumulation, ``x + 0.0`` for the hopless first node — exact on the
nonnegative energies involved). The full-width candidate rows here (``a`` up
to ``n`` with ``a > b`` masked to inf) are equivalent to numpy's ``a ≤ b``
slices: inf candidates never beat a finite min, and all-inf rows pick the
first index in both (``inf == inf``). tests/test_placement.py pins value
*and* parent arrays bitwise on every smoke config.

Numerics run in float64 under :func:`jax.enable_x64`, matching
:mod:`.partition_jax`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .cost import CostModel
from .graph import TaskGraph
from .placement import (
    PLACEMENT_COUNT,
    PlacementInputs,
    PlacementSpec,
    PlacementSweep,
    _finalize,
    placement_inputs,
)

__all__ = ["solve_placement_scan"]


@functools.lru_cache(maxsize=None)
def _placement_kernel(n: int, N: int, L: int, M: int, Z: int):
    """One jitted callable per problem shape (the jit cache key)."""
    big = n + 2
    idx = jnp.arange(n + 2)
    a_arr = jnp.arange(1, n + 1)
    i_arr = jnp.arange(1, n + 1)
    j_arr = jnp.arange(n + 1)

    def inner(energy_k: jnp.ndarray, thresh: jnp.ndarray):
        """Span-start DP for one (node, q_scale): S (n+2, n+2), A parents."""
        ec = jnp.where(energy_k <= thresh, energy_k, jnp.inf)
        ec_cols = ec[1 : n + 1, 1 : n + 1].T  # row b-1 = ec[1:n+1, b]
        S0 = jnp.full((n + 2, n + 2), jnp.inf).at[idx[1:], idx[:-1]].set(0.0)

        def step(S, xs):
            b, ec_col = xs
            # cand[i, a] = S[i, a-1] + E_k⟨a,b⟩, full width with a > b masked
            cand = S[:, 0:n] + jnp.where(a_arr <= b, ec_col, jnp.inf)[None, :]
            mn = jnp.min(cand, axis=-1)
            first = jnp.min(
                jnp.where(cand == mn[:, None], a_arr, big), axis=-1
            ).astype(jnp.int32)
            init_col = jnp.where(idx == b + 1, 0.0, jnp.inf)
            new_col = jnp.where(idx <= b, mn, init_col)
            new_A = jnp.where(idx <= b, first, 0).astype(jnp.int32)
            return S.at[:, b].set(new_col), new_A

        S, A_cols = lax.scan(step, S0, (jnp.arange(1, n + 1), ec_cols))
        A = jnp.zeros((n + 2, n + 2), jnp.int32).at[:, 1 : n + 1].set(A_cols.T)
        return S, A

    def outer(S_all, memok_all, hop, li, mi, zi):
        """Chain DP for one grid point (per-lane gathers inside the jit)."""
        S_z = S_all[:, zi]        # (N, n+2, n+2)
        ok_m = memok_all[:, mi]   # (N, n+2, n+2)
        hop_l = hop[li]           # (n+1,)

        def step(dp_prev, xs):
            k, S_k, ok_k = xs
            seg = jnp.where(ok_k, S_k, jnp.inf)
            base = dp_prev[0:n] + jnp.where(k >= 2, hop_l[0:n], 0.0)
            cand = base[None, :] + seg[1 : n + 1, 0 : n + 1].T
            cand = jnp.where(i_arr[None, :] <= j_arr[:, None], cand, jnp.inf)
            mn = jnp.min(cand, axis=-1)
            first = jnp.min(
                jnp.where(cand == mn[:, None], i_arr, big), axis=-1
            ).astype(jnp.int32)
            return mn, (mn, first)

        dp0 = jnp.full(n + 1, jnp.inf).at[0].set(0.0)
        _, (dp, parent) = lax.scan(
            step, dp0, (jnp.arange(1, N + 1), S_z, ok_m)
        )
        return dp, parent

    def kernel(energy, q_thresh, mem, mem_thresh, hop_total, li_idx, mi_idx, zi_idx):
        en_rep = jnp.repeat(energy, Z, axis=0)          # (N·Z, n+2, n+2)
        S_flat, A_flat = jax.vmap(inner)(en_rep, q_thresh.reshape(-1))
        S_all = S_flat.reshape(N, Z, n + 2, n + 2)
        A_all = A_flat.reshape(N, Z, n + 2, n + 2)
        memok_all = mem[None, None] <= mem_thresh[:, :, None, None]
        dp, parent = jax.vmap(
            lambda li, mi, zi: outer(S_all, memok_all, hop_total, li, mi, zi)
        )(li_idx, mi_idx, zi_idx)
        return S_all, A_all, dp, parent

    return jax.jit(kernel)


def solve_placement_scan(
    graph: TaskGraph,
    cost: CostModel,
    spec: PlacementSpec,
    *,
    inputs: Optional[PlacementInputs] = None,
) -> PlacementSweep:
    """Solve the whole placement grid in one batched jitted call,
    bit-identical to :func:`~repro.core.placement.solve_placement_numpy`."""
    if inputs is None:
        inputs = placement_inputs(graph, cost, spec)
    PLACEMENT_COUNT["scan"] += 1
    n, N = inputs.n_tasks, inputs.n_nodes
    L, M, Z = inputs.grid_shape
    # C-order lane indices over the (link, memory, q) grid
    li_idx = np.repeat(np.arange(L), M * Z)
    mi_idx = np.tile(np.repeat(np.arange(M), Z), L)
    zi_idx = np.tile(np.arange(Z), L * M)
    kernel = _placement_kernel(n, N, L, M, Z)
    with jax.enable_x64():
        S_all, A_all, dp, parent = kernel(
            jnp.asarray(inputs.energy),
            jnp.asarray(inputs.q_thresh),
            jnp.asarray(inputs.mem),
            jnp.asarray(inputs.mem_thresh),
            jnp.asarray(inputs.hop_total),
            jnp.asarray(li_idx),
            jnp.asarray(mi_idx),
            jnp.asarray(zi_idx),
        )
        inner_S = np.asarray(S_all)
        inner_A = np.asarray(A_all)
        outer_dp = np.asarray(dp).reshape(L, M, Z, N, n + 1)
        outer_parent = np.asarray(parent).reshape(L, M, Z, N, n + 1)
    e_total, k_used = _finalize(outer_dp, n, N)
    return PlacementSweep(
        inputs=inputs,
        backend="scan",
        e_total=e_total,
        k_used=k_used,
        outer_dp=outer_dp,
        outer_parent=outer_parent,
        inner_S=inner_S,
        inner_A=inner_A,
    )
