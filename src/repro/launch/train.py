"""End-to-end burst-checkpointed training driver.

Fault tolerance is the paper's Algorithm 1: train in bursts of k steps,
checkpoint + atomically commit the burst index after each burst, resume from
the committed index after any crash (the deterministic data pipeline
regenerates the exact batches). ``--crash-after-burst N`` injects a hard
process exit for testing; rerunning the same command resumes and converges
to the same trajectory.

On CPU this drives the reduced smoke configs (``--smoke``, default); the same
code path drives full configs on a real mesh.

Usage:
    python -m repro.launch.train --arch tinyllama-1.1b --steps 50 --smoke
    python -m repro.launch.train --arch tinyllama-1.1b --steps 50 --smoke \
        --crash-after-burst 2   # then rerun without the flag to resume
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.burst_ckpt import BurstCheckpointer, plan_burst_schedule
from ..configs import SMOKE_CONFIGS, get_config
from ..data.synthetic import SyntheticConfig, SyntheticData
from ..models import api
from ..models.sharding import rules_for, shardings_for_tree
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_production_mesh
from .steps import make_constrain


def train(arch: str, steps: int, batch: int, seq: int, burst_steps: int,
          ckpt_dir: str, smoke: bool = True, production_mesh: bool = False,
          crash_after_burst: int = -1, seed: int = 0, log_every: int = 10,
          lr: float = 1e-3):
    cfg = SMOKE_CONFIGS[arch] if smoke else get_config(arch)
    mesh = make_production_mesh() if production_mesh else make_host_mesh()
    rules = rules_for(cfg.family)
    cons = make_constrain(rules)
    adamw = AdamWConfig(lr=lr, warmup_steps=20)
    data = SyntheticData(SyntheticConfig(cfg.vocab, seq, batch, seed=seed))
    ck = BurstCheckpointer(ckpt_dir)

    def step_fn(params, opt_state, tokens, labels):
        def lf(p):
            batch_d = {"tokens": tokens, "labels": labels}
            if cfg.family == "vlm":
                batch_d["vision"] = jnp.zeros(
                    (tokens.shape[0], cfg.n_vision_tokens, cfg.d_model),
                    jnp.bfloat16)
            if cfg.family == "encdec":
                batch_d["audio"] = jnp.zeros(
                    (tokens.shape[0], cfg.n_audio_frames, cfg.d_model),
                    jnp.bfloat16)
            return api.loss(cfg, p, batch_d, constrain=cons, remat=True)

        (l, ce), grads = jax.value_and_grad(lf, has_aux=True)(params)
        new_p, new_o, stats = adamw_update(adamw, params, grads, opt_state)
        return new_p, new_o, l

    with mesh:
        restored = ck.restore()
        if restored is None:
            params, _ = api.init_params(cfg, jax.random.PRNGKey(seed), max_seq=seq)
            opt_state = adamw_init(params)
            start_burst = 0
            print(f"[train] fresh start: {arch} ({cfg.name}), "
                  f"{sum(np.prod(p.shape) for p in jax.tree.leaves(params)) / 1e6:.1f}M params")
        else:
            start_burst, state = restored
            params, opt_state = state["params"], state["opt_state"]
            print(f"[train] resumed from burst {start_burst} "
                  f"(step {start_burst * burst_steps})")

        jstep = jax.jit(step_fn, donate_argnums=(0, 1))
        n_bursts = (steps + burst_steps - 1) // burst_steps
        losses = []
        for burst in range(start_burst, n_bursts):
            t0 = time.time()
            for s in range(burst * burst_steps,
                           min((burst + 1) * burst_steps, steps)):
                b = data.batch(s)
                params, opt_state, loss = jstep(
                    params, opt_state, jnp.asarray(b["tokens"]),
                    jnp.asarray(b["labels"]))
                losses.append(float(loss))
                if s % log_every == 0:
                    print(f"[train] step {s:5d}  loss {float(loss):.4f}  "
                          f"({time.time() - t0:.1f}s into burst {burst})")
            ck.save(burst + 1, {"params": params, "opt_state": opt_state})
            print(f"[train] burst {burst + 1}/{n_bursts} committed "
                  f"({time.time() - t0:.1f}s)")
            if crash_after_burst == burst + 1:
                print("[train] injected crash! rerun to resume.")
                os._exit(1)
        print(f"[train] done: first loss {losses[0]:.4f} → last {losses[-1]:.4f}")
        return losses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--burst-steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--crash-after-burst", type=int, default=-1)
    ap.add_argument("--plan-bursts", action="store_true",
                    help="print the julienne checkpoint-cadence plan and exit")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.plan_bursts:
        part = plan_burst_schedule(args.steps, step_seconds=1.0,
                                   state_bytes=10**9, max_loss_seconds=60.0)
        print(part.summary())
        print("burst bounds:", part.bounds)
        return 0
    train(args.arch, args.steps, args.batch, args.seq, args.burst_steps,
          args.ckpt_dir, smoke=not args.full,
          production_mesh=args.production_mesh,
          crash_after_burst=args.crash_after_burst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
