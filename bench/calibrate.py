#!/usr/bin/env python3
"""Readings that the limit of `correct` is set from, many seeds in one
process (set-up and compilation are paid once per precision).

    python3 bench/calibrate.py --workload smollm-360m.p16.offline \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 45

For every seed of --seeds it runs the cell as the benchmark does, at the
configuration's precision, and prints the compared numbers.  For every
seed of --control-seeds it runs the program at the default matmul
precision, the program's own lower-precision path, which is the control
the limit has to fail.  One JSON line per run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    from bench import run as R
    cell = R.load_cell(args.workload, args.smoke)
    from bench import program, serve
    import jax
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    legs = [(int(s), None) for s in args.seeds.split(",") if s]
    legs += [(int(s), "default") for s in args.control_seeds.split(",") if s]
    for seed, prec in legs:
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                precision=prec)
        res = serve.run(cell, ns, time.perf_counter(), R.log)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "precision": prec or cell["config"]["matmul_precision"],
            "checks": {k: v[0] for k, v in res["checks"].items()},
            "per_request_gaps": res["per_request_gaps"],
            "attempted": res["attempted"], "failed": res["failed"],
            "serve_tok_s": res["reads"]["tokens_in_window"]
            / res["window_s"],
            "setup_s": res["setup_s"], "peak_bytes": res["peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
