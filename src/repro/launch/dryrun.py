import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh).

The two lines above MUST run before any jax import — jax locks the device
count at first initialization. Everything else (smoke tests, benches) sees
the real single CPU device because only this module sets the flag.

Per cell we record:
* ``compiled.memory_analysis()``  — bytes per device (proves it fits)
* ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline
* collective bytes by kind        — parsed from the optimized HLO, with
  while-loop trip-count correction (launch/roofline.py)

Usage:
    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod both --out experiments/dryrun
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from ..configs.base import REGISTRY, SHAPES, get_config, shape_applicable
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh
from .roofline import analyze_hlo, dominant_term, roofline_terms
from .steps import build_cell

# ensure all arch modules registered
from .. import configs as _configs  # noqa: F401


def _mem_stats(compiled) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    out[k] = int(v)
    except Exception as e:  # backend may not support it
        out["error"] = repr(e)
    return out


def _cost_stats(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and not k.startswith("utilization")}
    except Exception as e:
        return {"error_msg": 0.0}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: Optional[str] = None, remat: bool = True,
             verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "family": cfg.family,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    try:
        cell = build_cell(cfg, shape, mesh, remat=remat)
        lowered = cell.lower()
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

        mem = _mem_stats(compiled)
        cost = _cost_stats(compiled)
        hlo = compiled.as_text()
        stats = analyze_hlo(hlo)
        if save_hlo:
            with open(save_hlo, "w") as fh:
                fh.write(hlo)

        # Trip-count-corrected per-device numbers from the parsed HLO
        # (XLA:CPU cost_analysis counts while bodies once — recorded for
        # reference but not used for the roofline).
        flops_dev = stats.flops
        bytes_dev = stats.bytes
        coll_dev = stats.coll_bytes

        terms = roofline_terms(flops_dev, bytes_dev, coll_dev)
        model_flops = 6 * cfg.active_param_count() * shape.seq_len * shape.global_batch
        if shape.kind == "decode":
            model_flops = 6 * cfg.active_param_count() * shape.global_batch  # 1 token

        rec.update({
            "status": "ok",
            "t_lower_s": round(t_lower, 2),
            "t_compile_s": round(t_compile, 2),
            "n_chips": n_chips,
            "memory": mem,
            "cost_analysis": {k: v for k, v in sorted(cost.items())
                              if k in ("flops", "bytes accessed", "transcendentals")},
            "collective_bytes_by_kind": stats.coll_bytes_by_kind,
            "collective_count_by_kind": stats.coll_count_by_kind,
            "collective_bytes_total": coll_dev,
            "roofline": terms,
            "dominant": dominant_term(terms),
            "model_flops_global": model_flops,
            "useful_flops_ratio": (model_flops / (flops_dev * n_chips)
                                   if flops_dev else None),
        })
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
                  f"compile {t_compile:.1f}s  dominant={rec['dominant']}")
            print(f"  memory_analysis: {mem}")
            print(f"  cost_analysis: flops={flops_dev:.3g} "
                  f"bytes={bytes_dev:.3g} coll={coll_dev:.3g}")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: FAILED {rec['error']}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--out", default=None, help="directory for JSON records")
    args = ap.parse_args(argv)
    enable_compile_cache()

    archs = [args.arch] if args.arch else sorted(REGISTRY)
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    if args.out:
        os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                rec = run_cell(arch, shape, mp, save_hlo=args.save_hlo,
                               remat=not args.no_remat)
                if rec["status"] == "error":
                    failures += 1
                if args.out:
                    fn = f"{arch}_{shape}_{rec['mesh']}.json".replace("/", "-")
                    with open(os.path.join(args.out, fn), "w") as fh:
                        json.dump(rec, fh, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
