"""Energy-bounded serving: ``TrafficHarness.run`` over ``PlannedExecutor``.

Set-up runs the program's model configuration as the configuration file
states it, builds the plan table for the traffic's buckets, makes the
weights from the seed on the device (the reference's generator) and hands
them to the executor, sets the cycle budget to E_s plus a number of token
steps of the costliest bucket, and warms one request of each shape. A unit
of the window is one schedule of requests on the harness's virtual clock
(service time 1 per cycle), at a load that is a fixed share of the cycle
capacity; each request's latency is timed by a thin executor wrapper from
``open()`` to the ``step()`` that completes it.

The comparison holds the run to what the configuration states:

- ``served_logit_gap``: after the window, a sample of the completed
  requests drawn from the seed, with the longest among them, is run through
  the plain float32 reference, and each served token's reference logit is
  compared with the reference's best (greedy serving);
- ``cycle_budget_excess``: every committed cycle, priced from the plan
  table's step energy for its bucket as E_s plus the token steps that it
  ran, against the cycle budget;
- ``cycles_extra``: the cycles each request committed against the fewest
  that hold its token steps under the budget;
- ``ledger_error``: the energy that the program's ledger charged and that
  the harvest pool spent, each against the committed cycles as priced here;
- ``window_retraces``: jit traces of the serving steps inside the window.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import math
import time
from typing import Dict, List

import numpy as np

from chipbench import arrivals, work

PARAM_SEED = 0  # the executor's params-cache key that the weights go under
BUDGET_REL, BUDGET_ABS = 1e-9, 1e-12  # the solver's tolerance on a budget


def steps_per_cycle(e_startup: float, step: float, budget: float) -> int:
    """Token steps that one cycle holds: E_s and as many steps as fit."""
    k, acc = 1, e_startup + step
    while acc + step <= budget * (1 + BUDGET_REL) + BUDGET_ABS:
        acc += step
        k += 1
    return k


class TimedExecutor:
    """Delegates to a PlannedExecutor; gives each request the prompts the
    benchmark made (``seed`` carries the prompt's id) and times it from
    ``open()`` to the ``step()`` that completes it. ``fault`` plants a
    fault for the readings and tests: ``drop_commit`` runs the first two
    cycles of each request as one."""

    def __init__(self, inner, prompts: Dict[int, np.ndarray]):
        self.inner = inner
        self.planner = inner.planner
        self.prompts = prompts
        self.finished: List[dict] = []
        self.fault = None

    def open(self, batch, prompt_len, gen, *, seed, cycle_budget=None,
             plan=None, nvm=None, crash_hook=None):
        t_open = time.perf_counter()
        cont = self.inner.open(batch, prompt_len, gen, seed=PARAM_SEED,
                               cycle_budget=cycle_budget,
                               prompts=self.prompts[seed], plan=plan,
                               nvm=nvm, crash_hook=crash_hook)
        if self.fault == "drop_commit" and len(cont.cycles) > 1:
            _merge_first_cycles(cont)
        fields = {f.name: getattr(cont, f.name)
                  for f in dataclasses.fields(cont)}
        return _timed_continuation()(**fields, t_open=t_open,
                                     sink=self.finished)


def _merge_first_cycles(cont) -> None:
    from repro.core import BurstRuntime, Partition
    from repro.core.burst import burst_detail

    rt = cont.runtime
    (i, _), (_, j) = cont.cycles[:2]
    cycles = [(i, j)] + list(cont.cycles[2:])
    part = Partition(cycles, [burst_detail(rt.graph, rt.cost, a, b)
                              for a, b in cycles], None)
    cont.runtime = BurstRuntime(rt.graph, part, nvm=rt.nvm, cost=rt.cost)
    cont.cycles = cycles


@functools.lru_cache(maxsize=None)
def _timed_continuation():
    """A Continuation that records, per step, whether the cycle committed
    and how many token steps it ran, and its latency once complete. A
    subclass, not a patched instance: a closure over the instance's own
    bound method would make a reference cycle that keeps the request's
    committed KV caches on the device until the garbage collector runs."""
    from repro.launch.traffic import Continuation

    @dataclasses.dataclass
    class Timed(Continuation):
        t_open: float = 0.0
        sink: list = None
        steps: list = dataclasses.field(default_factory=list)

        def step(self) -> bool:
            rt = self.runtime
            index, tasks = rt.nvm.read_index(), rt.stats.tasks_run
            done = super().step()
            self.steps.append((rt.nvm.read_index() - index,
                               rt.stats.tasks_run - tasks))
            if done:
                r = self.request
                self.sink.append({"latency_s": time.perf_counter() - self.t_open,
                                  "shape": (r.batch, r.prompt_len, r.gen),
                                  "steps": self.steps})
            return done

    return Timed


class Driver:
    def __init__(self, cell, seed, ref, options):
        self.cell, self.ref, self.options = cell, ref, options
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.rng = np.random.default_rng([seed, 0])
        self.prompts: Dict[int, np.ndarray] = {}
        self.served: List[dict] = []
        self.guarantees = self._no_guarantee_readings()
        self.next_id = 0
        self.fault = options.get("fault")

    @staticmethod
    def _no_guarantee_readings() -> dict:
        return {"cycle_budget_excess": 0.0, "cycles_extra": 0,
                "ledger_error": 0.0, "window_retraces": 0}

    def reseed(self, seed: int) -> None:
        """Start over from ``seed`` on the same set-up, with the seed's own
        weights (readings only)."""
        self.seed = seed
        self.rng = np.random.default_rng([seed, 0])
        self.served.clear()
        self.guarantees = self._no_guarantee_readings()
        self.params = None
        for key in list(self.executor.inner._params):
            self.executor.inner._params[key] = None
        self.params = self.ref.make_params(self.config, seed)
        for key in list(self.executor.inner._params):
            self.executor.inner._params[key] = self.params

    # -- set-up ---------------------------------------------------------

    def _model_config(self, smoke: bool):
        """The program's configuration, run as the file states it: the
        program's registry has no per-model epsilon, so the file's is set
        on the config object that the executor and the table are given."""
        from repro.configs import resolve_config

        c = self.config
        mcfg = dataclasses.replace(resolve_config(c["arch"], smoke=smoke),
                                   norm_eps=c["rms_norm_eps"])
        stated = {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
                  "n_heads": c["num_attention_heads"],
                  "n_kv_heads": c["num_key_value_heads"],
                  "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
                  "qkv_bias": True, "tie_embeddings": c["tie_word_embeddings"],
                  "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
                  "hd": c["hidden_size"] // c["num_attention_heads"]}
        wrong = {k: (getattr(mcfg, k), v) for k, v in stated.items()
                 if getattr(mcfg, k) != v}
        if wrong:
            raise ValueError(f"the program's {mcfg.name} differs from the "
                             f"configuration file (program, file): {wrong}")
        return mcfg

    def _schedule(self, rate: float):
        tr = self.traffic
        return arrivals.schedule(tr["requests_per_schedule"], rate,
                                 tr["prompt"], tr["output"], tr["order_seed"])

    def setup(self) -> None:
        import jax
        from repro.launch.planner import build_table_for_arch
        from repro.launch.serve import PlannedExecutor
        from repro.launch.traffic import Request, TrafficHarness
        from repro.models import api

        tr = self.traffic
        smoke = bool(self.options.get("smoke", False))
        mcfg = self._model_config(smoke)
        batch = tr["batch"]
        shapes = arrivals.shapes(self._schedule(1.0))
        buckets = sorted({(batch, p + g) for p, g in shapes})
        t = time.perf_counter()
        table = build_table_for_arch(mcfg, buckets, n_q=tr["plan_q_points"],
                                     smoke=smoke)
        print(f"[serve] plan table for {buckets}: "
              f"{time.perf_counter() - t:.3f} s", flush=True)
        inner = PlannedExecutor(mcfg, table, smoke=smoke)
        params = self.ref.make_params(self.config, self.seed)
        abstract, _ = api.init_params(inner.cfg, None)
        tree = lambda x: jax.tree.map(lambda a: (a.shape, a.dtype), x)
        if tree(abstract) != tree(params):
            raise ValueError("the reference's weight tree does not match the "
                             "program's parameter layout")
        for _, max_seq in buckets:
            inner._params[(PARAM_SEED, max_seq)] = params
        self.params = params
        self.e_s = inner.planner.e_startup
        self.budget = self.e_s + tr["budget_steps"] * max(
            table.lookup(b, s, None).e_total for b, s in buckets)
        # The plan table's step energy at the budget, and the fewest cycles
        # that hold each shape's token steps.
        self.step_e = {(batch, s): table.lookup(batch, s, self.budget).e_total
                       for _, s in buckets}
        self.cycles_for = {
            (batch, p, g): math.ceil(g / steps_per_cycle(
                self.e_s, self.step_e[(batch, p + g)], self.budget))
            for p, g in shapes}
        sched = self._schedule(1.0)
        self.mean_cycles = float(np.mean(
            [self.cycles_for[(batch, p, g)] for _, p, g in sched]))
        self.rate = tr["load"] / self.mean_cycles
        self.arrivals = self._schedule(self.rate)
        self.executor = TimedExecutor(inner, self.prompts)
        self.harness = TrafficHarness(self.executor, cycle_budget=self.budget,
                                      service_time=1.0, keep_tokens=True)
        print(f"[serve] shapes {shapes}, cycle budget {self.budget!r}, "
              f"cycles per shape {self.cycles_for}, rate {self.rate!r} per "
              f"cycle", flush=True)
        # Warm each shape with one request of its own.
        warm = [Request(rid=k, batch=batch, prompt_len=p, gen=g, time=float(k),
                        seed=self._new_prompt(batch, p))
                for k, (p, g) in enumerate(shapes)]
        rep = self.harness.run(warm)
        if rep.completed != len(warm):
            raise RuntimeError(f"warm-up completed {rep.completed} of {len(warm)}")
        self.executor.finished.clear()

    def _new_prompt(self, batch: int, prompt: int) -> int:
        pid = self.next_id
        self.next_id += 1
        self.prompts[pid] = self.rng.integers(
            0, self.config["vocab_size"], (batch, prompt), dtype=np.int32)
        return pid

    # -- the window -----------------------------------------------------

    def run_unit(self) -> dict:
        from repro.launch.traffic import Request

        if self.fault == "retrace":
            import jax

            jax.clear_caches()
        self.executor.fault = self.fault
        batch = self.traffic["batch"]
        reqs = [Request(rid=rid, batch=batch, prompt_len=p, gen=g, time=t,
                        seed=self._new_prompt(batch, p))
                for rid, (t, p, g) in enumerate(self.arrivals)]
        n_done = len(self.executor.finished)
        rep = self.harness.run(reqs)
        finished = self.executor.finished[n_done:]
        u = {"requests": len(reqs), "completed": rep.completed,
             "tokens": 0, "prompt_tokens": 0, "decode_steps": 0, "flops": 0,
             "latencies_s": [f["latency_s"] for f in finished]}
        for r in reqs:
            if r.rid not in rep.tokens:
                continue
            b, p, g = r.batch, r.prompt_len, r.gen
            u["tokens"] += b * g
            u["prompt_tokens"] += b * p
            u["decode_steps"] += g - 1
            u["flops"] += (work.lm_forward_flops(self.config, b * p, (p + 1) / 2, False)
                           + work.lm_forward_flops(self.config, b, 0, True)
                           - work.lm_forward_flops(self.config, b, 0, False)
                           + work.lm_forward_flops(self.config, b * (g - 1),
                                                   p + g / 2, True))
            self.served.append({"prompts": self.prompts[r.seed],
                                "tokens": np.asarray(rep.tokens[r.rid]),
                                "length": p + g})
        for r in reqs:
            self.prompts.pop(r.seed, None)
        self._check_guarantees(finished, rep)
        return u

    def _check_guarantees(self, finished, rep) -> None:
        """Price every committed cycle from the plan table and hold the run
        to the budget, the fewest cycles and a balanced ledger."""
        g = self.guarantees
        priced = 0.0
        for f in finished:
            b, p, n = f["shape"]
            step = self.step_e[(b, p + n)]
            committed = [tasks for c, tasks in f["steps"] if c]
            for tasks in committed:
                e = self.e_s + tasks * step
                priced += e
                g["cycle_budget_excess"] = max(
                    g["cycle_budget_excess"], (e - self.budget) / self.budget)
            g["cycles_extra"] += abs(len(committed) - self.cycles_for[f["shape"]])
        for spent in (rep.ledger.charged_total(), rep.energy_spent):
            g["ledger_error"] = max(g["ledger_error"],
                                    abs(spent - priced) / max(priced, 1e-300))
        g["window_retraces"] += rep.retraces

    @staticmethod
    def attempted_failed(units):
        n = sum(u["requests"] for u in units)
        return n, n - sum(u["completed"] for u in units)

    def release(self) -> None:
        import jax

        self.harness = self.executor = self.params = None
        gc.collect()
        jax.clear_caches()

    # -- the comparison -------------------------------------------------

    def sample(self) -> List[dict]:
        """Requests to compare, drawn from the seed: one of the longest and
        as many others as the traffic's ``compare`` asks for."""
        rng = np.random.default_rng([self.seed, 2])
        k = min(self.traffic["compare"], len(self.served))
        if not k:
            return []
        top = max(s["length"] for s in self.served)
        longest = [i for i, s in enumerate(self.served) if s["length"] == top]
        first = int(rng.choice(longest))
        rest = [i for i in range(len(self.served)) if i != first]
        picked = [first] + sorted(int(i) for i in rng.choice(rest, k - 1, replace=False))
        return [self.served[i] for i in picked]

    def numbers(self, control: bool = False) -> dict:
        """The guarantees' readings and the widest gap by which a served
        token's reference logit lies below the reference's best. With
        ``control``, the token that the reference computed with fp8 matmul
        operands (the precision below the program's bfloat16) puts first
        stands in for the served one."""
        out = {k: float(v) for k, v in self.guarantees.items()}
        picked = self.sample()
        if len(picked) < self.traffic["compare"]:
            return out
        params = self.ref.make_params(self.config, self.seed)
        gap = 0.0
        n = 0
        for s in picked:
            logits = self.ref.logits_for(self.config, params, s["prompts"], s["tokens"])
            tokens = s["tokens"]
            if control:
                low = self.ref.logits_for(self.config, params, s["prompts"],
                                          s["tokens"], quant=True)
                tokens = np.asarray(low).argmax(axis=-1)
            gaps = self.ref.served_gaps(logits, tokens)
            gap = max(gap, float(gaps.max()))
            n += gaps.size
        print(f"[serve] compared {n} tokens of {len(picked)} requests with the "
              f"reference{' (control)' if control else ''}", flush=True)
        out["served_logit_gap"] = gap
        return out
