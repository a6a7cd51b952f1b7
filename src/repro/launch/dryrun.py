import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and extract roofline inputs (memory_analysis, cost_analysis,
collective bytes from optimized HLO).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out runs/dryrun]

The XLA_FLAGS line above MUST stay the first statement — jax locks the host
device count on first init (see the module-level comment in DESIGN.md §5).
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs, obs
from repro.launch import hlo_cost, roofline
from repro.launch.mesh import make_hierarchical_mesh, make_production_mesh
from repro.launch.specs import SHAPES, build

log = obs.get_logger("dryrun")


def _mem_analysis_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out and ma is not None:
        out["repr"] = str(ma)
    return out


def run_one(arch: str, shape: str, *, multi_pod: bool = False, downlink: str = "marina:perm",
            verbose: bool = True, save_hlo: str | None = None,
            serve_layout: str = "serve", remat_policy=None,
            train_act_model_sharded: bool = False,
            hierarchical_workers: int = 0) -> dict:
    if hierarchical_workers:
        mesh = make_hierarchical_mesh(hierarchical_workers)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    built = build(arch, shape, mesh, downlink_spec=downlink, serve_layout=serve_layout,
                  remat_policy=remat_policy,
                  train_act_model_sharded=train_act_model_sharded)
    jitted = jax.jit(built.fn, in_shardings=built.in_shardings)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        lowered = jitted.lower(*built.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware per-device totals (cost_analysis counts while bodies
    # once and misses collectives — see launch/hlo_cost.py)
    totals = hlo_cost.analyze(hlo)
    mem = _mem_analysis_dict(compiled)
    flops_dev = totals["flops"]
    bytes_dev = totals["bytes"]
    coll_dev = totals["coll_total"]
    cfg = built.meta["cfg"]
    mf = roofline.model_flops(cfg, built.meta["kind"], built.meta["global_batch"], built.meta["seq"])
    terms = roofline.roofline_terms(flops_dev, bytes_dev, coll_dev,
                                    device_kind=roofline.TARGET_KIND)
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": (f"wk{hierarchical_workers}x{16//hierarchical_workers}x16" if hierarchical_workers
                 else f"{'2x16x16' if multi_pod else '16x16'}"),
        "chips": chips,
        "kind": built.meta["kind"],
        "downlink": downlink if built.meta["kind"] == "train" else None,
        "serve_layout": serve_layout if built.meta["kind"] != "train" else None,
        "remat_policy": remat_policy,
        "window_override": built.meta.get("window"),
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": totals["coll"],
        "collective_total_per_device": coll_dev,
        "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "memory_analysis": mem,
        "model_flops": mf,
        "useful_flops_ratio": (mf / (flops_dev * chips)) if flops_dev else None,
        "roofline": terms,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }
    if verbose:
        dom = terms["dominant"].replace("_s", "")
        log.info(
            f"{arch:26s} {shape:12s} mesh={rec['mesh']:8s} "
            f"compile={t_compile:6.1f}s flops/dev={flops_dev:.3e} bytes/dev={bytes_dev:.3e} "
            f"coll/dev={coll_dev:.3e} dominant={dom}"
        )
    # structured twin of the log line: compile timings land in the same
    # JSONL stream as benchmark events (REPRO_OBS_JSONL)
    obs.default_tracker().log(
        {
            "dryrun": {
                "arch": arch, "shape": shape, "mesh": rec["mesh"],
                "t_lower_s": t_lower, "t_compile_s": t_compile,
                "flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
                "collective_total_per_device": coll_dev,
                "dominant": terms["dominant"],
            }
        }
    )
    if save_hlo:
        import gzip

        with gzip.open(save_hlo, "wt") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--downlink", default="marina:perm")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--serve-layout", default="serve", choices=["serve", "tp", "tp_attn_rep"])
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--train-act-model-sharded", action="store_true")
    ap.add_argument("--hierarchical-workers", type=int, default=0)
    args = ap.parse_args()

    archs = list(configs.ALIASES) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    log.info(f"skip (cached) {tag}")
                    continue
                try:
                    hlo_path = os.path.join(args.out, tag + ".hlo.gz") if args.save_hlo else None
                    rec = run_one(arch, shape, multi_pod=mp, downlink=args.downlink,
                                  save_hlo=hlo_path, serve_layout=args.serve_layout,
                                  remat_policy=args.remat_policy,
                                  train_act_model_sharded=args.train_act_model_sharded,
                                  hierarchical_workers=args.hierarchical_workers)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((tag, repr(e)))
    if failures:
        log.error(f"FAILURES ({len(failures)}):")
        for tag, err in failures:
            log.error(f"  {tag} {err[:200]}")
        raise SystemExit(1)
    log.info("all requested combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
