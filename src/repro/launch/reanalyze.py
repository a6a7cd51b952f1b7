"""Re-derive roofline terms from saved .hlo.gz artifacts (no recompilation).

Usage: PYTHONPATH=src python -m repro.launch.reanalyze runs/dryrun_v2 [out_dir]
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys

from repro import obs
from repro.launch import hlo_cost, roofline

log = obs.get_logger("reanalyze")


def reanalyze(dirpath: str, out_dir: str | None = None):
    out_dir = out_dir or dirpath
    os.makedirs(out_dir, exist_ok=True)
    for jf in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        hf = jf.replace(".json", ".hlo.gz")
        if not os.path.exists(hf):
            continue
        rec = json.load(open(jf))
        totals = hlo_cost.analyze(gzip.open(hf, "rt").read())
        rec["flops_per_device"] = totals["flops"]
        rec["bytes_per_device"] = totals["bytes"]
        rec["collective_bytes_per_device"] = totals["coll"]
        rec["collective_total_per_device"] = totals["coll_total"]
        rec["roofline"] = roofline.roofline_terms(
            totals["flops"], totals["bytes"], totals["coll_total"],
            device_kind=roofline.TARGET_KIND,
        )
        if rec.get("model_flops") and totals["flops"]:
            rec["useful_flops_ratio"] = rec["model_flops"] / (totals["flops"] * rec["chips"])
        out = os.path.join(out_dir, os.path.basename(jf))
        json.dump(rec, open(out, "w"), indent=1)
        t = rec["roofline"]
        log.info(f"{rec['arch']:26s} {rec['shape']:12s} {rec['mesh']:8s} "
                 f"dom={t['dominant'][:4]} bound={t['bound_s']:.3e}")
        obs.default_tracker().log(
            {
                "reanalyze": {
                    "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
                    "flops_per_device": rec["flops_per_device"],
                    "bytes_per_device": rec["bytes_per_device"],
                    "bound_s": t["bound_s"], "dominant": t["dominant"],
                }
            }
        )


if __name__ == "__main__":
    reanalyze(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else None)
