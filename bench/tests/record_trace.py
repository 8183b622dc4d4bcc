#!/usr/bin/env python3
"""Record the small chip trace that test_trace.py reads: smollm-360m.p16
at its configuration's engine shape (the kept trace was recorded at 16
slots), a few decode steps after one prefill, traced.

    python3 bench/tests/record_trace.py <out_dir>     # on one TPU v5e
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import json
    import numpy as np
    import jax
    from bench import families, program
    from bench import trace as TR
    program.enable_compile_cache()
    with open(os.path.join(ROOT, "bench", "configs",
                           "smollm-360m.p16.json")) as f:
        conf = json.load(f)
    m, eng = conf["model"], conf["engine"]
    fam = families.load(conf)
    cfg = program.model_config(conf["arch"], m, fam)
    with jax.default_matmul_precision("highest"):
        params = fam.serving_params(1, m, cfg)
        engine = program.make_engine(params, cfg, eng, 1)
        rng = np.random.default_rng(1)
        for _ in range(8):
            engine.submit(rng.integers(0, m["vocab"], 100), 8)
        while engine.counters["decode_steps"] < 2:
            engine.step()
        log = os.path.join(out_dir, "log")
        jax.profiler.start_trace(log)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
        jax.profiler.stop_trace()
    src = TR.find_xplane(log)
    shutil.copy(src, os.path.join(out_dir, "smollm_decode.xplane.pb"))
    dev, spans = TR.read_events(src)
    print({k: len(v) for k, v in dev.items()}, len(spans),
          os.path.getsize(src))
    names = sorted({n for v in dev.values() for n, _, _ in v})
    print("\n".join(names))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
