"""Batched serving driver: prefill + decode with sequence-sharded KV caches,
optionally scheduled from a precomputed plan table.

Serves a batch of prompts: one prefill step builds the padded KV cache
(recurrent state for SSM/hybrid archs), then greedy decode steps extend it.
On CPU this drives the smoke configs; the same path lowers for the
production meshes (decode_32k / long_500k dry-run cells).

With ``--plan-table`` the request is **energy-bounded**: the request shape
is bucketed into a :class:`repro.core.plan_table.PlanTable` (an O(1) lookup
— zero partitioner solves, zero jit retraces on the request path, pinned by
tests/test_serve_plan.py), the token steps are grouped into cycles that fit
``--energy-budget``, and the whole request executes as a task graph through
:class:`repro.core.runtime.BurstRuntime`: every cycle boundary commits the
decode state to NVM, so a mid-request power failure resumes from the last
committed cycle instead of restarting the request. Scheduling changes,
results never do: planned and unplanned serving produce identical token
sequences.

Usage:
    python -m repro.launch.serve --arch qwen3-4b --prompt-len 32 --gen 16
    python -m repro.launch.planner --arch qwen3-4b --buckets 2x24,2x48 \
        --out plan.npz
    python -m repro.launch.serve --arch qwen3-4b --batch 2 --prompt-len 8 \
        --gen 8 --plan-table plan.npz --energy-budget 0.5
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from collections.abc import MutableMapping
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import resolve_config
from ..models import api
from ..models.common import compute_copy
from ..obs.metrics import METRICS
from ..obs.trace import PID_RUNTIME, PID_TRAFFIC, TRACER
from ..models.sharding import rules_for
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh
from .steps import make_constrain
from .traffic import Continuation, Request

# Trace-time counters for the serving request path (incremented only when
# XLA actually re-traces; the serving regression tests pin these at zero
# across repeated planned *and* unplanned requests of the same shape).
# Registry-backed (repro.obs.metrics) but still a plain dict in every way
# existing consumers rely on.
TRACE_COUNT = METRICS.counter_dict("serve.trace_count", ("prefill", "decode"))


# Bytes of decode state in the packet each opened request passes from step to
# step: ``recurrent`` (fixed-size SSM / conv state) and ``kv`` (attention
# caches at the request's bucket length).
STATE_BYTES = METRICS.counter_dict("serve.state_bytes", ("recurrent", "kv"))

# Serving copies of the weights (``compute_copy``) looked up at each open:
# ``miss`` made one, ``hit`` reused the copy of a tree already seen.
PARAM_CAST = METRICS.counter_dict("serve.param_cast", ("miss", "hit"))


def reset_trace_counts() -> None:
    """Zero the process-global retrace counters (test isolation). The jit
    caches themselves are untouched — this resets observability, not
    compilation state. Consumers that can't rely on a reset (the traffic
    harness) snapshot-and-diff instead of reading absolutes. Thin alias for
    the registry reset; ``repro.obs.metrics.reset_all()`` covers it too."""
    TRACE_COUNT.reset()


@functools.lru_cache(maxsize=None)
def _host_mesh():
    """One mesh object per process: jit caches are keyed on the ambient
    mesh, so re-creating it per request would defeat the no-retrace path."""
    return make_host_mesh()


def _resolve(arch: str, smoke: bool):
    # the shared repro.configs.resolve_config — serve, planner, DSE, and the
    # façade all bucket (arch, smoke) → ModelConfig identically
    return resolve_config(arch, smoke=smoke)


@functools.lru_cache(maxsize=None)
def _step_fns(arch: str, smoke: bool, max_seq: int, donate: bool = False):
    """Cached jitted (prefill, decode) for both serving paths.

    Cached per (arch, smoke, max_seq, donate) so repeated requests reuse the
    same compiled executables. The planned path uses ``donate=False``: a
    replayed cycle must be able to re-read the committed cache from NVM, and
    donation would invalidate it. The unplanned path uses ``donate=True``
    (cache donation on decode — donation changes performance, never values)
    to keep its original fast-path semantics while still hitting this cache
    instead of rebuilding ``jax.jit`` wrappers per call. Always pass
    ``donate=`` by keyword: ``lru_cache`` keys positional and keyword calls
    differently, and a mixed style would silently double-compile.
    """
    cfg = _resolve(arch, smoke)
    cons = make_constrain(rules_for(cfg.family))

    def _prefill(params, batch):
        TRACE_COUNT["prefill"] += 1
        return api.prefill(cfg, params, batch, max_seq, constrain=cons)

    def _decode(params, cache, tok, pos):
        TRACE_COUNT["decode"] += 1
        return api.decode_step(cfg, params, cache, tok, pos, constrain=cons)

    decode = (jax.jit(_decode, donate_argnums=(1,)) if donate
              else jax.jit(_decode))
    return jax.jit(_prefill), decode


def _pre_batch(cfg, prompts) -> Dict[str, Any]:
    batch = int(np.shape(prompts)[0])
    out: Dict[str, Any] = {"tokens": prompts}
    if cfg.family == "vlm":
        out["vision"] = jnp.zeros(
            (batch, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        out["audio"] = jnp.zeros(
            (batch, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16)
    return out


@functools.lru_cache(maxsize=None)
def _cache_nbytes(cfg, batch: int, max_seq: int) -> Dict[str, int]:
    return api.cache_bytes(cfg, batch, max_seq)


def _in_span(name: str, fn, *args):
    """``fn(*args)``, inside a ``name`` span on the runtime track while
    tracing is on; off, the site costs one ``TRACER.enabled`` check."""
    if not TRACER.enabled:
        return fn(*args)
    with TRACER.span(name, cat="serve", pid=PID_RUNTIME):
        return fn(*args)


def _append_token(seq: Optional[np.ndarray], tok) -> np.ndarray:
    # The host reads the step's token back, so it waits for the step.
    t = np.asarray(tok)
    return t if seq is None else np.concatenate([seq, t], axis=1)


def _request_graph(cfg, params, batch, prompt_len, gen, max_seq,
                   prefill_fn, decode_fn, step_energy):
    """The request as a Ladybirds task graph: task 1 = prefill (emits token
    1), task k = decode step k (emits token k). Each task reads the previous
    decode state packet and writes the next (SSA); the final task writes the
    ``sequence`` output. Task bodies are pure functions of their declared
    inputs — the cached jitted steps are deterministic — so replayed cycles
    are idempotent, exactly the contract BurstRuntime's recovery relies on.

    Traced, each body is a ``serve.prefill`` or ``serve.decode`` span whose
    child ``serve.token_sync`` is the token's readback and the sequence's
    concatenation.
    """
    from ..core import GraphBuilder

    b = GraphBuilder()
    b.packet("prompts", batch * prompt_len * 4, external=True)
    state_bytes = sum(_cache_nbytes(cfg, batch, max_seq).values()) + batch * 4
    for k in range(gen - 1):
        b.packet(f"state{k}", state_bytes)
    b.packet("sequence", batch * gen * 4, keep=True)

    def emit(k: int, cache, tok, seq: np.ndarray) -> Dict[str, Any]:
        if k == gen - 1:
            return {"sequence": seq}
        return {f"state{k}": {"cache": cache, "tok": tok, "seq": seq}}

    def mk_prefill():
        def fn(inp):
            logits, cache = prefill_fn(params, _pre_batch(cfg, inp["prompts"]))
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            seq = _in_span("serve.token_sync", _append_token, None, tok)
            return emit(0, cache, tok, seq)
        return lambda inp: _in_span("serve.prefill", fn, inp)

    def mk_decode(k: int):
        def fn(inp):
            st = inp[f"state{k - 1}"]
            logits, cache = decode_fn(
                params, st["cache"], st["tok"], jnp.int32(prompt_len + k - 1)
            )
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            seq = _in_span("serve.token_sync", _append_token, st["seq"], tok)
            return emit(k, cache, tok, seq)
        return lambda inp: _in_span("serve.decode", fn, inp)

    b.task("prefill", reads=("prompts",),
           writes=("sequence",) if gen == 1 else ("state0",),
           cost=step_energy, fn=mk_prefill())
    for k in range(1, gen):
        b.task(f"decode{k}", reads=(f"state{k - 1}",),
               writes=("sequence",) if k == gen - 1 else (f"state{k}",),
               cost=step_energy, fn=mk_decode(k))
    return b.build()


class WeightTrees(MutableMapping):
    """``(seed, max_seq)`` → float32 weight tree, as a dict, with each tree's
    serving copy (:func:`~repro.models.common.compute_copy`) made at its
    first :meth:`serving` lookup. Keys that hold the same tree share one
    copy, found by the tree's identity with no walk of the tree. A copy
    lives only while some key holds its tree: replacing or deleting a key's
    tree drops copies no key needs. Trees are values: one is replaced, never
    updated in place."""

    def __init__(self) -> None:
        self._trees: Dict[Any, Any] = {}
        self._copies: Dict[int, Any] = {}   # id(tree) → its serving copy

    def __getitem__(self, key):
        return self._trees[key]

    def __setitem__(self, key, tree) -> None:
        self._trees[key] = tree
        self._drop_unheld()

    def __delitem__(self, key) -> None:
        del self._trees[key]
        self._drop_unheld()

    def __iter__(self):
        return iter(self._trees)

    def __len__(self) -> int:
        return len(self._trees)

    def _drop_unheld(self) -> None:
        held = {id(t) for t in self._trees.values()}
        for i in [i for i in self._copies if i not in held]:
            del self._copies[i]

    def serving(self, key):
        tree = self._trees[key]
        copy = self._copies.get(id(tree))
        if copy is None:
            PARAM_CAST["miss"] += 1
            copy = self._copies[id(tree)] = compute_copy(tree)
        else:
            PARAM_CAST["hit"] += 1
        return copy


class PlannedExecutor:
    """Reusable per-request executor for the planned path.

    Owns the pieces that amortize across a request stream — the resolved
    config, the :class:`~repro.launch.planner.ServePlanner` (O(1) lookups),
    a params cache keyed on ``(seed, max_seq)`` (:class:`WeightTrees`: the
    steps run on each tree's serving copy), and the process-wide jitted
    step cache — and :meth:`open`\\ s each request as a
    :class:`~repro.launch.traffic.Continuation` whose energy cycles commit
    one :meth:`~repro.launch.traffic.Continuation.step` at a time. The
    single-request `serve()` path drives one continuation to completion; the
    continuous-traffic harness (:class:`repro.launch.traffic.TrafficHarness`)
    interleaves cycles of many.
    """

    def __init__(self, arch: str, plan_table, smoke: bool = True) -> None:
        from ..core.plan_table import PlanTableError
        from .planner import as_planner

        self.arch = arch
        self.smoke = smoke
        self.planner = as_planner(plan_table)
        self.cfg = _resolve(arch, smoke)
        if self.planner.table.arch != self.cfg.name:
            raise PlanTableError(
                f"plan table was built for {self.planner.table.arch!r} but "
                f"this request is for {self.cfg.name!r}"
            )
        self._params = WeightTrees()
        self._next_rid = 0

    def _params_for(self, seed: int, max_seq: int):
        """The serving copy of the weights for ``(seed, max_seq)``, made
        from the seed on first use unless a tree was put there."""
        key = (seed, max_seq)
        if key not in self._params:
            with _host_mesh():
                params, _ = api.init_params(
                    self.cfg, jax.random.PRNGKey(seed), max_seq=max_seq)
            self._params[key] = params
        return self._params.serving(key)

    def make_prompts(self, batch: int, prompt_len: int, seed: int = 0):
        return jax.random.randint(jax.random.PRNGKey(seed + 1),
                                  (batch, prompt_len), 0, self.cfg.vocab)

    def open(self, batch: int, prompt_len: int, gen: int, *, seed: int = 0,
             cycle_budget: Optional[float] = None, prompts=None, plan=None,
             nvm=None, crash_hook=None) -> Continuation:
        """Open one request as a steppable Continuation.

        ``plan`` short-circuits the table lookup (the harness already looked
        it up on the admission path — passing it back avoids double-counting
        ``planner.stats``). External inputs are seeded only on a fresh NVM
        (committed index 0), so reopening against a mid-request NVM resumes
        rather than restarts — the crash-recovery contract.

        Traced as one ``serve.open`` span carrying the ``rid`` this executor
        gives the request (a traffic harness relabels the continuation with
        its own request afterwards).
        """
        kw = dict(seed=seed, cycle_budget=cycle_budget, prompts=prompts,
                  plan=plan, nvm=nvm, crash_hook=crash_hook)
        if not TRACER.enabled:
            return self._open(batch, prompt_len, gen, **kw)
        with TRACER.span("serve.open", cat="serve", pid=PID_TRAFFIC,
                         rid=self._next_rid, batch=batch,
                         prompt_len=prompt_len, gen=gen):
            return self._open(batch, prompt_len, gen, **kw)

    def _open(self, batch, prompt_len, gen, *, seed, cycle_budget, prompts,
              plan, nvm, crash_hook) -> Continuation:
        from ..core import BurstRuntime, CostModel, LinearTransfer, Partition
        from ..core.burst import burst_detail
        from .planner import request_cycles

        max_seq = prompt_len + gen
        if plan is None:
            plan = self.planner.plan_for(batch, max_seq, cycle_budget)
        with _host_mesh():
            params = self._params_for(seed, max_seq)
            if prompts is None:
                prompts = self.make_prompts(batch, prompt_len, seed)
            prefill_fn, decode_fn = _step_fns(self.arch, self.smoke, max_seq,
                                              donate=False)
            graph = _request_graph(self.cfg, params, batch, prompt_len, gen,
                                   max_seq, prefill_fn, decode_fn,
                                   step_energy=plan.e_total)
        for kind, n in _cache_nbytes(self.cfg, batch, max_seq).items():
            STATE_BYTES[kind] += n
        cycles = request_cycles(gen, plan.e_total, cycle_budget,
                                e_startup=self.planner.e_startup)
        cost = CostModel(e_startup=self.planner.e_startup,
                         read=LinearTransfer(0.0, 0.0),
                         write=LinearTransfer(0.0, 0.0),
                         name="request-cycles")
        part = Partition(
            cycles, [burst_detail(graph, cost, i, j) for (i, j) in cycles],
            None,
        )
        rt = BurstRuntime(graph, part, nvm=nvm, cost=cost,
                          crash_hook=crash_hook)
        if rt.nvm.read_index() == 0:
            rt.seed_inputs({"prompts": np.asarray(prompts)})
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, batch=batch, prompt_len=prompt_len, gen=gen,
                      seed=seed)
        return Continuation(request=req, plan=plan, cycles=list(cycles),
                            runtime=rt, e_startup=self.planner.e_startup,
                            scope=_host_mesh)

    def warmup(self, shapes, cycle_budget: Optional[float] = None) -> None:
        """Pre-compile: run one throwaway request per ``(batch, prompt_len,
        gen, seed)`` shape so jit tracing happens outside any measured or
        admission-controlled window."""
        for (batch, prompt_len, gen, seed) in shapes:
            cont = self.open(batch, prompt_len, gen, seed=seed,
                             cycle_budget=cycle_budget)
            cont.run_to_completion()


def _serve_planned(arch, batch, prompt_len, gen, smoke, seed,
                   plan_table, energy_budget, nvm, crash_hook, report):
    ex = PlannedExecutor(arch, plan_table, smoke=smoke)
    cont = ex.open(batch, prompt_len, gen, seed=seed,
                   cycle_budget=energy_budget, nvm=nvm, crash_hook=crash_hook)
    t0 = time.time()
    out = cont.run_to_completion()
    dt = time.time() - t0
    seqs = jnp.asarray(out)
    print(f"[serve] {arch}: planned batch={batch} "
          f"prefill({prompt_len} tok)+{gen - 1} decode steps in "
          f"{len(cont.cycles)} energy cycles ({dt * 1e3:.1f} ms total); "
          f"plan: {cont.plan.summary()}")
    print(f"[serve] first sequences: {np.asarray(seqs)[:2, :8]}")
    if report is not None:
        report.update(
            plan=cont.plan, cycles=list(cont.cycles),
            runtime_stats=cont.runtime.stats,
            planner_stats=dict(ex.planner.stats), nvm=cont.runtime.nvm,
        )
    return seqs


def serve(arch: str, batch: int, prompt_len: int, gen: int, smoke: bool = True,
          seed: int = 0, plan_table=None, energy_budget: Optional[float] = None,
          nvm=None, crash_hook=None, report: Optional[dict] = None):
    """Serve one batched request.

    ``plan_table`` (path / PlanTable / ServePlanner) switches to the
    energy-bounded planned path described in the module docstring; ``nvm``
    and ``crash_hook`` are forwarded to the BurstRuntime so tests can inject
    power failures mid-request, and ``report`` (a dict) receives the plan,
    cycle bounds, and runtime stats.
    """
    if gen < 1:
        raise ValueError("gen must be >= 1 (prefill emits the first token)")
    if plan_table is not None:
        return _serve_planned(arch, batch, prompt_len, gen, smoke, seed,
                              plan_table, energy_budget, nvm, crash_hook,
                              report)
    planned_only = {"energy_budget": energy_budget, "nvm": nvm,
                    "crash_hook": crash_hook, "report": report}
    misused = [k for k, v in planned_only.items() if v is not None]
    if misused:
        raise ValueError(
            f"{misused} require plan_table: without a plan table there are "
            "no energy cycles, NVM commits, or crash resumability"
        )

    cfg = _resolve(arch, smoke)
    mesh = _host_mesh()
    max_seq = prompt_len + gen

    with mesh:
        params, _ = api.init_params(cfg, jax.random.PRNGKey(seed), max_seq=max_seq)
        params = compute_copy(params)
        prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                     (batch, prompt_len), 0, cfg.vocab)
        pre_batch = _pre_batch(cfg, prompts)

        # the same cached executables as the planned path (donate=True keeps
        # the decode cache-donation fast path) — previously fresh
        # jax.jit(lambda ...) wrappers here retraced on every call
        prefill, decode = _step_fns(arch, smoke, max_seq, donate=True)
        t0 = time.time()
        logits, cache = prefill(params, pre_batch)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        t_pre = time.time() - t0

        out = [tok]
        t1 = time.time()
        for i in range(gen - 1):
            logits, cache = decode(params, cache, tok, jnp.int32(prompt_len + i))
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        t_dec = time.time() - t1
        seqs = jnp.concatenate(out, axis=1)
        print(f"[serve] {arch}: batch={batch} prefill({prompt_len} tok) "
              f"{t_pre * 1e3:.1f} ms, decode {gen - 1} steps "
              f"{t_dec * 1e3 / max(gen - 1, 1):.1f} ms/tok")
        print(f"[serve] first sequences: {np.asarray(seqs)[:2, :8]}")
        return seqs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--plan-table", default=None,
                    help="precomputed PlanTable (.npz) — enables the "
                         "energy-bounded planned path")
    ap.add_argument("--energy-budget", type=float, default=None,
                    help="per-cycle energy budget (units of the table's "
                         "cost model; default: unbounded)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace_event JSON (Perfetto-loadable)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry snapshot as JSON")
    ap.add_argument("--calibration", default=None,
                    help="measured-cost calibration JSON (from "
                         "`launch/dse.py --calibrate`): probe the plan table "
                         "against the measured profile before serving and "
                         "refuse stale plans (requires --plan-table)")
    ap.add_argument("--drift-tol", type=float, default=0.05,
                    help="relative drift tolerance for the --calibration "
                         "probe (default 0.05)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.trace_out:
        TRACER.configure(enabled=True)
    if args.calibration:
        if not args.plan_table:
            ap.error("--calibration requires --plan-table")
        from ..core.calibration import MeasuredCostTable
        from ..core.plan_table import PlanTable, probe_plan_table

        measured = MeasuredCostTable.from_json(args.calibration)
        n = probe_plan_table(PlanTable.load(args.plan_table),
                             _resolve(args.arch, not args.full),
                             k=4, measured=measured,
                             drift_tol=args.drift_tol)
        print(f"[serve] calibration probe: {n} cells of {args.plan_table} "
              f"within {args.drift_tol:.1%} of the measured profile "
              f"({measured.n_samples} samples) — serving")
    serve(args.arch, args.batch, args.prompt_len, args.gen,
          smoke=not args.full, plan_table=args.plan_table,
          energy_budget=args.energy_budget)
    if args.trace_out:
        n_events = TRACER.write(args.trace_out)
        print(f"[serve] wrote {n_events} trace events to {args.trace_out}")
    if args.metrics_out:
        METRICS.dump_json(args.metrics_out, tool="serve", arch=args.arch)
        print(f"[serve] wrote metrics snapshot to {args.metrics_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
