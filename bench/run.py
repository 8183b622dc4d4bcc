#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <config>.<mix> --seed N --seconds S \
        --trace 0|1

A cell `<config>.<mix>` is the configuration bench/configs/<config>.json
under the traffic mix bench/mixes/<mix>.json; BENCHMARK.json at the root of
the checkout lists the cells, the chips each asks for, and the metrics each
reports.  Every metric is a reader of its own, bench/metrics/<name>.py.

With --trace 0 the result line gives the cell's end-to-end metrics; with
--trace 1 the window is traced by the JAX profiler and the line gives its
per-layer metrics, the device's busy time and a breakdown.  Both decide
`correct` by the comparison with the plain reference of the configuration's
model family (bench/families/<family>.py; "dense" where the configuration
names none).

The run fails, and prints no result, off a TPU or on fewer chips than the
cell asks for.  --smoke (rehearsal only) runs the configuration's and the
mix's `smoke` sizes on whatever backend JAX has; --precision (control
only) runs the program at another matmul precision than the
configuration states.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# libtpu would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.join(ROOT, "bench")


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(name: str, smoke: bool) -> dict:
    """The cell's configuration, mix and the benchmark's entries for it."""
    spec = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = _load_json(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    mix = _load_json(os.path.join(BENCH, "mixes", f"{w['traffic']}.json"))
    if smoke:
        conf = _merge(conf, conf.get("smoke", {}))
        mix = _merge(mix, mix.get("smoke", {}))

    def applies(metric):
        return name in metric.get("workloads", [name])
    return {"name": name, "entry": w, "config": conf, "mix": mix,
            "model": conf["model"], "engine": conf.get("engine"),
            "check": conf["check"],
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def read_metric(name: str, ctx: dict):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--precision", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    cell = load_cell(args.workload, args.smoke)

    # the program first: outside a checkout this fails before JAX touches
    # a device
    from bench import program
    import jax
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    chips = int(cell["entry"]["chips"])
    if not args.smoke:
        if devs[0].platform != "tpu":
            log(f"JAX found no TPU (platform {devs[0].platform!r})")
            return 2
        if program.kops.dispatch_key() != (True, False, False):
            log(f"the Pallas kernels are off or interpreted "
                f"{program.kops.dispatch_key()}")
            return 2
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
        return 2
    log(f"{args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={devs[0].device_kind!r} x{len(devs)}"
        f" jax={jax.__version__}")

    kind = cell["config"]["kind"]
    if kind == "serve":
        from bench import serve as runner
    else:
        raise SystemExit(f"bench: unknown configuration kind {kind!r}")
    res = runner.run(cell, args, T_PROC0, log)
    return finish(cell, args, res, devs, chips)


def finish(cell, args, res, devs, chips) -> int:
    from bench import families, lastline, trace as TR, work
    fam = families.load(cell["config"])
    traced = bool(args.trace)
    kind = devs[0].device_kind
    try:
        pk = work.peaks(kind)
    except KeyError:
        if not args.smoke:
            raise
        pk = work.peaks("TPU v5 lite")     # rehearsal arithmetic only
    ctx = {"setup_s": res["setup_s"], "window_s": res["window_s"],
           "reads": res["reads"], "peaks": pk, "trace": None,
           "step_work": None, "kernels": fam.KERNELS}
    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": int(res["peak_bytes"])}
    breakdown = None
    if traced:
        t0 = time.perf_counter()
        try:
            dev, spans = TR.read_events(TR.find_xplane(res["log_dir"]))
        finally:
            shutil.rmtree(res["log_dir"], ignore_errors=True)
        win = [(s, e) for n, s, e in spans if n == "bench.window"]
        red = TR.reduce_events(dev, spans, win[-1] if win else None)
        ctx["trace"] = red
        rows = res["reads"]["work_rows"]
        if rows is not None:
            ctx["step_work"] = fam.step_work(res["reads"]["model"], rows,
                                             res["reads"]["collected"])
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = TR.breakdown(red)
        log(f"trace read in {time.perf_counter() - t0:.2f}s: "
            f"{sum(len(v) for v in dev.values())} device ops, "
            f"{len(spans)} spans, chips {red['chips']}")
    wanted = cell["per_layer"] if traced else cell["end_to_end"]
    metrics = {}
    for mdef in wanted:
        v = read_metric(mdef["name"], ctx)
        if v is not None:
            metrics[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}
    checks = {}
    ok = True
    for name, (value, limit, sense) in res["checks"].items():
        if value is not None and value != value:
            value = None                  # NaN: nothing was compared
        checks[name] = {"value": value, "limit": limit}
        if limit is None or value is None:
            ok = False
        elif sense == "max" and not value <= limit:
            ok = False
        elif sense == "min" and not value >= limit:
            ok = False
    obj = {"correct": ok, "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        obj["breakdown"] = breakdown
    obj["checks"] = checks
    required = [] if traced else [m["name"] for m in wanted]
    lastline.emit(obj, traced=traced,
                  expected={m["name"]: m["unit"] for m in wanted},
                  required_metrics=required)
    return 0


if __name__ == "__main__":
    sys.exit(main())
