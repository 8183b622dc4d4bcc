"""Serving launcher: batched generation with posit-quantized weights/KV.

    # synchronized dense-cache batch (the original engine)
    python -m repro.launch.serve --arch smollm-360m --smoke \
        --batch 4 --prompt-len 32 --max-new 16 --posit p16

    # continuous batching over the paged posit KV pool
    python -m repro.launch.serve --arch smollm-360m --smoke --engine paged \
        --batch 4 --prompt-len 32 --max-new 16 --posit p16 --requests 16

    # mesh-sharded paged serving (data x model axes; here 8-way forced-CPU)
    python -m repro.launch.serve --arch smollm-360m --smoke --engine paged \
        --batch 8 --mesh 4x2 --host-devices 8

    # the same run under the JAX profiler: the trace in DIR holds the paged
    # engine's phase spans (engine.step, engine.schedule, ..., engine.fetch)
    python -m repro.launch.serve --arch smollm-360m --smoke --engine paged \
        --profile DIR

Runs PTQ (quant/ptq.py) on freshly-initialized (or checkpointed) weights,
then serves synthetic traffic.  The paged engine draws mixed prompt lengths
in [prompt-len/4, prompt-len] so admission/retirement actually interleave.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=["dense", "paged"], default="dense")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch (dense) / sequence slots (paged)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--posit", choices=["off", "p8", "p16"], default="p16")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    # paged-engine knobs
    ap.add_argument("--requests", type=int, default=None,
                    help="paged: total requests to serve (default 2*batch)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged: disable content-addressed prefix caching "
                         "of KV pages")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="paged: shard the serving step over a "
                         "(data, model) mesh, e.g. 4x2")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N CPU host devices (sets XLA_FLAGS; must "
                         "run before jax initializes)")
    # graceful-degradation / chaos knobs (paged engine)
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="paged: bound the admission queue; overflow "
                         "submissions resolve `rejected` with a "
                         "retry-after hint instead of queueing forever")
    ap.add_argument("--ttl-steps", type=int, default=None,
                    help="paged: per-request TTL in engine steps; "
                         "exceeded -> `expired`, pages return to the pool")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="paged: per-request wall-clock deadline (s)")
    ap.add_argument("--chaos", default=None, metavar="KIND=P[,KIND=P...]",
                    help="paged: seeded fault injection, e.g. "
                         "'step_fault=0.05,nar_poison=0.02,"
                         "page_poison=0.02,straggle=0.1' "
                         "(see serving/faults.py)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="run under jax.profiler.trace(DIR); the paged "
                         "engine's phase spans land in that trace")
    args = ap.parse_args()

    if args.host_devices:
        # append (not prepend): XLA applies the *last* duplicate flag, so an
        # inherited force_host_platform_device_count must not win over the
        # explicit request
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.host_devices}")

    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.checkpoint import store
    from repro.core.types import P8_2, P16_2
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.transformer import init_params
    from repro.quant.policy import PositPolicy
    from repro.quant.ptq import quantize_for_serving
    from repro.serving.engine import PHASES, PagedServingEngine, generate

    enable_compile_cache()
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())
    pcfg = {"p8": P8_2, "p16": P16_2}.get(args.posit)
    policy = PositPolicy(weights=pcfg, kv_cache=pcfg) if pcfg else PositPolicy()
    get = configs.get_smoke if args.smoke else configs.get_config
    cfg = get(args.arch, policy=policy)

    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        step, restored = store.restore_latest(args.ckpt_dir, {"params": params})
        if step is not None:
            params = restored["params"]
            print(f"[serve] loaded checkpoint step {step}")
    if pcfg is not None:
        params = quantize_for_serving(params, pcfg)
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
        print(f"[serve] PTQ {pcfg}: weights now {nbytes/1e6:.1f} MB")

    if args.engine == "dense":
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab)
        t0 = time.time()
        with profile:
            out = generate(params, cfg, prompts, args.max_new,
                           temperature=args.temperature)
            out.block_until_ready()
        dt = time.time() - t0
        print(f"[serve] generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.max_new / dt:.1f} tok/s incl. compile)")
        print(out[:, :12])
        return

    # paged continuous batching: mixed-length synthetic traffic
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh
        d, m = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_serving_mesh(d, m)
        print(f"[serve] mesh: data={d} x model={m} over "
              f"{d * m} {jax.devices()[0].platform} devices")
    n_req = args.requests or 2 * args.batch
    rng = np.random.default_rng(1)
    cap = args.prompt_len + args.max_new
    width = max(2, -(-cap // args.page_size))
    from repro.serving.backends import layout_for
    layout = layout_for(cfg)
    kinds = ",".join(f"{b.kind}:{b.backend}" for b in layout.backends)
    print(f"[serve] cache backends: {kinds}; per-seq cache at "
          f"{cap} tokens = "
          f"{layout.cache_bytes_per_seq(cap, args.page_size) / 1e3:.1f} KB")
    chaos = None
    if args.chaos:
        from repro.serving.faults import ChaosConfig
        kv = dict(part.split("=") for part in args.chaos.split(","))
        chaos = ChaosConfig(seed=args.chaos_seed,
                            **{f"p_{k}": float(v) for k, v in kv.items()})
        print(f"[serve] chaos: {chaos}")
    eng = PagedServingEngine(
        params, cfg, max_seqs=args.batch, page_size=args.page_size,
        table_width=width, prefill_chunk=args.prefill_chunk,
        temperature=args.temperature,
        prefix_cache=not args.no_prefix_cache, mesh=mesh,
        max_waiting=args.max_waiting, default_ttl_steps=args.ttl_steps,
        default_deadline_s=args.deadline_s, chaos=chaos)
    reqs = []
    for _ in range(n_req):
        plen = int(rng.integers(max(1, args.prompt_len // 4),
                                args.prompt_len + 1))
        reqs.append((rng.integers(0, cfg.vocab, plen), args.max_new))
    t0 = time.time()
    with profile:
        results = eng.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(v) for v in results.values())
    stats = eng.stats()
    print(f"[serve] paged: {len(results)} requests, {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok / dt:.1f} tok/s incl. compile); "
          f"stats={stats}")
    from repro.serving.engine import OUTCOMES
    outcome_line = " ".join(f"{k}={stats.get(k, 0)}" for k in OUTCOMES)
    print(f"[serve] outcomes: submitted={stats.get('submitted', 0)} "
          f"{outcome_line}")
    print(f"[serve] robustness: straggler_steps="
          f"{stats.get('straggler_steps', 0)} "
          f"step_latency_ms p50={stats.get('step_latency_p50_ms', 0.0):.1f} "
          f"p99={stats.get('step_latency_p99_ms', 0.0):.1f}")
    run, grid = stats["decode_kv_blocks_run"], stats["decode_kv_blocks_grid"]
    print("[serve] host phases ms: " + " ".join(
        f"{ph}={stats[f'phase_ms_{ph}']:.1f}" for ph in PHASES)
        + f" | decode kv blocks run/grid={run}/{grid}"
        + (f" ({run / grid:.1%})" if grid else ""))
    if results:
        first = results[min(results)]
        print(f"[serve] rid {min(results)}: {first[:12]}")


if __name__ == "__main__":
    main()
