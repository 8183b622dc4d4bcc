"""Roofline table assembler: reads experiments/dryrun/*.json (produced by
launch/dryrun.py) and emits the EXPERIMENTS.md §Roofline table.

Per (arch x shape) single-pod cell:
  compute/memory/collective terms (s), dominant bottleneck,
  MODEL_FLOPS (6ND / 6 N_active D) vs HLO FLOPs ratio, fit-in-HBM check.
"""
from __future__ import annotations

import glob
import json
import os

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")
HBM_BYTES = 16e9   # v5e per chip


def load_cells(mesh: str = "pod"):
    rows = []
    for fn in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        rec = json.load(open(fn))
        if rec.get("mesh") != mesh or rec.get("posit") is False:
            continue
        rows.append(rec)
    return rows


def table(mesh: str = "pod"):
    rows = []
    for rec in load_cells(mesh):
        row = {"arch": rec["arch"], "shape": rec["shape"],
               "status": rec["status"]}
        if rec["status"] == "skip":
            row["note"] = rec.get("reason")
        elif rec["status"] == "ok":
            row.update({
                "strategy": rec.get("strategy"),
                "t_compute_s": rec.get("t_compute_s"),
                "t_memory_s": rec.get("t_memory_s"),
                "t_collective_s": rec.get("t_collective_s"),
                "bottleneck": rec.get("bottleneck"),
                "hbm_per_dev_gb": round(
                    (rec.get("mem_argument_size_in_bytes", 0)
                     + rec.get("mem_temp_size_in_bytes", 0)) / 1e9, 2),
                "fits_hbm": (rec.get("mem_argument_size_in_bytes", 0)
                             + rec.get("mem_temp_size_in_bytes", 0)) < HBM_BYTES,
            })
            mf = rec.get("model_flops_analytic")
            hf = rec.get("flops_per_device")
            nd = rec.get("n_devices", 256)
            if mf and hf:
                row["model_hlo_flops_ratio"] = round(mf / nd / hf, 3)
                # roofline fraction: useful-FLOPs time over the dominant term
                t_dom = max(rec.get("t_compute_s", 0),
                            rec.get("t_memory_s", 0),
                            rec.get("t_collective_s", 0))
                from repro.launch.analysis import TARGET_KIND, peaks
                t_useful = mf / nd / peaks(TARGET_KIND)["flops_bf16"]
                row["roofline_fraction"] = round(t_useful / t_dom, 4) if t_dom else None
        else:
            row["note"] = rec.get("error", "")[:160]
        rows.append(row)
    return rows


def markdown(mesh: str = "pod") -> str:
    rows = table(mesh)
    hdr = ("| arch | shape | strat | t_comp | t_mem | t_coll | bottleneck | "
           "HBM/dev GB | MODEL/HLO | roofline frac | note |")
    sep = "|" + "---|" * 11
    lines = [hdr, sep]
    for r in rows:
        if r["status"] == "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r.get('strategy','')} | "
                f"{r['t_compute_s']:.3g} | {r['t_memory_s']:.3g} | "
                f"{r['t_collective_s']:.3g} | {r['bottleneck']} | "
                f"{r['hbm_per_dev_gb']} | "
                f"{r.get('model_hlo_flops_ratio','')} | "
                f"{r.get('roofline_fraction','')} | |")
        else:
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | "
                         f"{r['status']} | - | - | - | {r.get('note','')} |")
    return "\n".join(lines)


def run(report):
    import time
    t0 = time.time()
    rows = table("pod")
    ok = sum(1 for r in rows if r["status"] == "ok")
    skip = sum(1 for r in rows if r["status"] == "skip")
    fail = len(rows) - ok - skip
    report("roofline_table", (time.time() - t0) * 1e6,
           {"cells_ok": ok, "cells_skip": skip, "cells_fail": fail})
