#!/usr/bin/env python3
"""Smoke test of the posit serving and training path on a TPU.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --chips 4       # the four-chip mesh path only

One chip runs three phases on smollm-360m at its published widths, with
random weights drawn from --seed:

  serve-p16  PagedServingEngine with PTQ posit16 weights and a posit16 KV
             pool: 8 slots, 16-token pages, one fixed page-table width,
             16 requests with prompts of 64-512 tokens, 16 new tokens each;
  serve-p8   the first 4 of those requests again at posit8;
  train      3 steps of launch.train's path (seq 512, global batch 8,
             posit16 STE weights).

Each phase runs the Pallas kernels twice: at the default matmul
precision, which is what the system runs and what is timed, and at
"highest", where Mosaic contracts f32 operands in f32.  Both are compared
on the same chip with the jnp reference path (kernels switched off, f32
matmuls at "highest").  Serving compares the logits behind the first and
the last generated token of every request; the reference gets the last
one teacher-forced, from a prefill of the prompt plus the tokens the
kernel leg generated before it, so it checks every decode step's KV
writes and reads.  Training compares the loss and the gradient norm of
every step.  The bf16 control (the reference at the default precision,
against the reference at "highest") is reported beside each reading: the
"highest" leg's limit sits between its own reading and that control, so a
kernel fault the size of bf16 rounding still fails it.

--chips 4 serves olmoe-1b-7b at full width, cut to 2 layers, through the
mesh-sharded engine on a (data=2, model=2) mesh: data parallelism plus
expert-parallel grouped GEMM and the per-block psum.  It compares its
logits, in the same way, with one chip's kernels teacher-forced on the
mesh's tokens, at both precisions, and runs nothing else.

A line per phase reports compile seconds, steady step time, tokens, the
process's peak device bytes, and the reference difference.  The script
exits non-zero, before printing its result line, when the platform is not
a TPU, when the kernels run in interpret mode or are switched off, when a
kernel leg falls back to a jnp path, or when a tolerance is exceeded.  The
last line of a passing run is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Limits: "default" legs are the kernels at the default matmul precision,
# "highest" legs the kernels at "highest"; both against the reference at
# "highest".  Logit errors are the max absolute difference over every
# compared request, position and vocab entry, divided by the largest
# reference logit magnitude; loss and grad-norm errors are relative, per
# step.  Set from one TPU v5e run at --seed 0 (reading / bf16 control):
#   logits p16  default 1.616e-2  highest 1.050e-4  control 1.647e-2
#   logits p8   default 3.384e-2  highest 2.619e-3  control 3.700e-2
#   loss        default 2.338e-4  highest 1.122e-6  control 2.294e-4
#   grad norm   default 1.007e-3  highest 4.534e-6  control 1.027e-3
# Mosaic's default f32 contraction rounds like the bf16 control, so the
# default legs are held to twice the control; the "highest" legs, which
# differ from the reference only by posit KV rounding, sit between their
# reading and the control, where a kernel fault of bf16 size fails them.
# The mesh's reference is one chip's kernels; on four v5e chips:
#   logits olmoe  default 1.578e-1  highest 6.547e-7  control 1.654e-1
# (at the default precision bf16 rounding moves olmoe's logits ten times
# as far as smollm's, as much on one chip as across the mesh).
LOGIT_TOL = {"p16": {"default": 3e-2, "highest": 1e-3},
             "p8": {"default": 7e-2, "highest": 1e-2}}
LOSS_TOL = {"default": 5e-4, "highest": 1e-5}
GNORM_TOL = {"default": 2e-3, "highest": 5e-5}
MESH_LOGIT_TOL = {"default": 3e-1, "highest": 1e-3}
PRECISIONS = ("default", "highest")

SLOTS, PAGE, CHUNK, MAX_NEW = 8, 16, 128, 16
MIN_PROMPT, MAX_PROMPT = 64, 512
TABLE_WIDTH = -(-(MAX_PROMPT + MAX_NEW) // PAGE)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def fallback_count() -> int:
    """Sum of every kernel-fallback counter (they count at trace time)."""
    from repro.kernels.ops import BWD_FALLBACKS, RECURRENT_FALLBACKS
    from repro.models.moe import DENSE_MOE_FALLBACKS
    from repro.serving.paged_kv import GATHER_FALLBACKS
    return sum(sum(c.values()) for c in (GATHER_FALLBACKS,
                                         DENSE_MOE_FALLBACKS,
                                         RECURRENT_FALLBACKS, BWD_FALLBACKS))


def matmul_precision(name: str):
    """A leg's matmul precision; "default" leaves it as the system runs."""
    import jax
    if name == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(name)


@contextlib.contextmanager
def reference_path(precision: str = "highest"):
    """The jnp reference: Pallas dispatch off, f32 matmuls at `precision`
    ("default" gives the bf16 control).  Jitted steps key their caches on
    the dispatch state, so nothing traced with the kernels is reused
    here."""
    prev = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "0"
    try:
        with matmul_precision(precision):
            yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_USE_PALLAS")
        else:
            os.environ["REPRO_USE_PALLAS"] = prev


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, **fields):
    from repro.kernels.ops import pallas_interpret
    fields["interpret"] = pallas_interpret()
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[chip_smoke] {phase} {body}", flush=True)


def logit_error(got: dict, want: dict) -> float:
    """got/want: {rid: [vocab] logit rows, one per compared position}."""
    import numpy as np
    check(set(got) == set(want) and got,
          f"compared requests differ: {sorted(got)} vs {sorted(want)}")
    for r in want:
        check(np.all(np.isfinite(got[r])), f"non-finite logits, rid {r}")
    diff = max(float(np.max(np.abs(got[r] - want[r]))) for r in want)
    scale = max(float(np.max(np.abs(want[r]))) for r in want)
    return diff / max(scale, 1e-30)


def rel_errors(got, want) -> list[float]:
    check(len(got) == len(want), f"{len(got)} vs {len(want)} records")
    return [abs(a - b) / abs(b) for a, b in zip(got, want)]


def make_requests(seed: int, vocab: int, n: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(MIN_PROMPT, MAX_PROMPT + 1, size=n)
    return [rng.integers(0, vocab, size=int(s)).astype(np.int32)
            for s in lens]


def serving_params(cfg, pcfg, seed: int):
    """Random f32 weights from `seed`, then PTQ to `pcfg`, both jitted."""
    import jax
    from repro.models.transformer import init_params
    from repro.quant.ptq import quantize_for_serving
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    return jax.jit(functools.partial(quantize_for_serving, cfg=pcfg))(params)


def make_engine(params, cfg, mesh=None):
    from repro.serving.engine import PagedServingEngine
    return PagedServingEngine(
        params, cfg, max_seqs=SLOTS, page_size=PAGE, table_width=TABLE_WIDTH,
        prefill_chunk=CHUNK, bucket_pages=False, mesh=mesh,
        record_logits=True)


def serve(params, cfg, prompts, max_new, mesh=None):
    """Drain `prompts` through a fresh engine.  Returns the logits behind
    the first and the last generated token of each request ({rid: [2,
    vocab]}), the generated tokens, timing and counts, and the engine."""
    import numpy as np
    eng = make_engine(params, cfg, mesh)
    t0 = time.perf_counter()
    out = eng.run([(p, max_new) for p in prompts])
    wall = time.perf_counter() - t0
    st = eng.stats()
    lost = {k: st[k] for k in ("rejected", "expired", "failed_nar",
                               "failed_fault")}
    check(len(out) == len(prompts),
          f"{len(out)} of {len(prompts)} requests completed: {lost}")
    check(all(len(v) == max_new for v in eng.token_logits.values()),
          "a request is missing recorded logits")
    steps = st["prefill_steps"] + st["decode_steps"]
    ends = {r: np.stack([v[0], v[-1]]) for r, v in eng.token_logits.items()}
    return ends, out, {
        "wall_s": wall, "steps": steps,
        "tokens": sum(len(v) for v in out.values()),
        "step_p50_ms": st["step_latency_p50_ms"]}, eng


def teacher_forced(params, cfg, prompts, generated) -> dict:
    """The logits a leg that generated `generated` should have seen at its
    first and its last token, each from a prefill on one chip: of the
    prompt, and of the prompt plus every generated token but the last."""
    import numpy as np
    n = len(prompts)
    forced = [np.concatenate([p, generated[i][:-1]]).astype(np.int32)
              for i, p in enumerate(prompts)]
    ends, _, _, _ = serve(params, cfg, list(prompts) + forced, 1)
    return {i: np.stack([ends[i][0], ends[n + i][0]]) for i in range(n)}


def kernel_vs_reference(params, cfg, prompts, mesh=None,
                        reference=reference_path):
    """Serve `prompts` on the kernels at each of PRECISIONS (on `mesh`),
    and compare each leg with `reference(precision)` teacher-forced on its
    tokens.  Returns the errors by precision, the bf16 control error
    (`reference` at "default" against it at "highest"), the default leg's
    timing and engine, and the fallbacks the kernel legs counted."""
    fb0 = fallback_count()
    legs = {}
    for prec in PRECISIONS:
        with matmul_precision(prec):
            legs[prec] = serve(params, cfg, prompts, MAX_NEW, mesh)
    fallbacks = fallback_count() - fb0
    errs, wants = {}, {}
    for prec, (ends, out, _, _) in legs.items():
        gen = [out[i] for i in range(len(prompts))]
        with reference("highest"):
            wants[prec] = teacher_forced(params, cfg, prompts, gen)
        errs[prec] = logit_error(ends, wants[prec])
    hi_gen = [legs["highest"][1][i] for i in range(len(prompts))]
    with reference("default"):
        ctl = teacher_forced(params, cfg, prompts, hi_gen)
    return (errs, logit_error(ctl, wants["highest"]), *legs["default"][2:],
            fallbacks)


def serve_phase(name: str, pcfg, prompts, seed: int) -> str:
    import jax
    from repro import configs
    from repro.quant.policy import PositPolicy
    cfg = configs.get_config("smollm-360m",
                             policy=PositPolicy(weights=pcfg, kv_cache=pcfg))
    params = serving_params(cfg, pcfg, seed)
    jax.block_until_ready(params)

    # warm-up drain of one long prompt: compiles the prefill-chunk and the
    # decode step (one page-table width, so no other shape exists)
    fb0 = fallback_count()
    t0 = time.perf_counter()
    serve(params, cfg, [prompts[0][:CHUNK + 1]], 2)
    compile_s = time.perf_counter() - t0
    fallbacks = fallback_count() - fb0
    errs, ctl, tm, _, fb = kernel_vs_reference(params, cfg, prompts)
    fallbacks += fb
    check(fallbacks == 0, f"{name}: {fallbacks} kernel fallbacks")
    tol = LOGIT_TOL[name.split("-")[1]]
    report(name, compile_s=f"{compile_s:.2f}",
           steady_step_ms=f"{1e3 * tm['wall_s'] / tm['steps']:.3f}",
           step_p50_ms=f"{tm['step_p50_ms']:.3f}", steps=tm["steps"],
           requests=len(prompts), tokens_served=tm["tokens"],
           tok_per_s=f"{tm['tokens'] / tm['wall_s']:.1f}",
           peak_bytes=peak_bytes(),
           max_logit_err=f"{errs['default']:.3e}", tol=tol["default"],
           max_logit_err_highest=f"{errs['highest']:.3e}",
           tol_highest=tol["highest"], bf16_control_err=f"{ctl:.3e}",
           fallbacks=fallbacks)
    for prec in PRECISIONS:
        check(errs[prec] <= tol[prec],
              f"{name}: {prec} logit error {errs[prec]:.3e} > {tol[prec]}")
    return name


def train_phase(seed: int, steps: int = 3) -> str:
    from repro.launch.train import run_configs
    from repro.training.trainer import train_loop

    cfg, opt_cfg, data_cfg = run_configs(
        "smollm-360m", steps=steps, seq_len=512, global_batch=8, lr=3e-4,
        posit="p16")

    def run():
        return train_loop(cfg, opt_cfg, data_cfg, steps, log_every=1,
                          seed=seed)[2]

    fb0 = fallback_count()
    hist = {}
    for prec in PRECISIONS:
        with matmul_precision(prec):
            hist[prec] = run()
    fallbacks = fallback_count() - fb0
    check(fallbacks == 0, f"train: {fallbacks} kernel fallbacks")
    with reference_path("highest"):
        ref = run()
    with reference_path("default"):
        ctl = run()

    def errs(key, h):
        return rel_errors([r[key] for r in h], [r[key] for r in ref])

    loss = {p: max(errs("loss", h)) for p, h in hist.items()}
    gnorm = {p: max(errs("grad_norm", h)) for p, h in hist.items()}
    h = hist["default"]
    check(len(h) == steps, "missing step records")
    steady = (h[-1]["wall_s"] - h[0]["wall_s"]) / (steps - 1)
    report("train", compile_s=f"{h[0]['wall_s']:.2f}",
           steady_step_ms=f"{1e3 * steady:.3f}",
           tokens_trained=steps * data_cfg.global_batch * data_cfg.seq_len,
           peak_bytes=peak_bytes(),
           loss=",".join(f"{r['loss']:.6f}" for r in h),
           ref_loss=",".join(f"{r['loss']:.6f}" for r in ref),
           max_loss_rel_err=f"{loss['default']:.3e}",
           max_loss_rel_err_highest=f"{loss['highest']:.3e}",
           max_gnorm_rel_err=f"{gnorm['default']:.3e}",
           max_gnorm_rel_err_highest=f"{gnorm['highest']:.3e}",
           bf16_control_loss_err=f"{max(errs('loss', ctl)):.3e}",
           bf16_control_gnorm_err=f"{max(errs('grad_norm', ctl)):.3e}",
           loss_tol=LOSS_TOL, gnorm_tol=GNORM_TOL, fallbacks=fallbacks)
    for prec, h in hist.items():
        check(all(math.isfinite(r["loss"]) for r in h), "non-finite loss")
        check(loss[prec] <= LOSS_TOL[prec],
              f"train: {prec} loss error {loss[prec]:.3e} > "
              f"{LOSS_TOL[prec]}")
        check(gnorm[prec] <= GNORM_TOL[prec],
              f"train: {prec} grad-norm error {gnorm[prec]:.3e} > "
              f"{GNORM_TOL[prec]}")
    return "train"


def mesh_phase(seed: int) -> str:
    """olmoe-1b-7b (2 layers) on a (data=2, model=2) mesh vs one chip."""
    import jax
    from repro import configs
    from repro.core.types import P16_2
    from repro.launch.mesh import make_serving_mesh
    from repro.quant.policy import PositPolicy

    cfg = configs.get_config("olmoe-1b-7b",
                             policy=PositPolicy(weights=P16_2,
                                                kv_cache=P16_2))
    cfg = dataclasses.replace(cfg, name="olmoe-1b-7b-2l", n_layers=2)
    params = serving_params(cfg, P16_2, seed)
    prompts = make_requests(seed, cfg.vocab, SLOTS)
    mesh = make_serving_mesh(data=2, model=2)
    check(len({d.id for d in mesh.devices.flat}) == 4,
          f"mesh holds {mesh.devices.size} distinct devices, not 4")

    fb0 = fallback_count()
    t0 = time.perf_counter()
    serve(params, cfg, [prompts[0][:CHUNK + 1]], 2, mesh)
    compile_s = time.perf_counter() - t0
    # the reference is one chip's kernels, teacher-forced on the mesh's
    # tokens; its bf16 control is one chip at default vs "highest"
    errs, ctl, tm, eng, _ = kernel_vs_reference(
        params, cfg, prompts, mesh=mesh, reference=matmul_precision)
    fallbacks = fallback_count() - fb0
    check(fallbacks == 0, f"mesh: {fallbacks} kernel fallbacks")
    # the weights and the page pools must live on all four devices
    held = set()
    for leaf in jax.tree_util.tree_leaves((eng.params, eng.pages)):
        held |= {d.id for d in leaf.sharding.device_set}
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat]
    report("mesh-olmoe-2x2", compile_s=f"{compile_s:.2f}",
           steady_step_ms=f"{1e3 * tm['wall_s'] / tm['steps']:.3f}",
           steps=tm["steps"], requests=len(prompts),
           tokens_served=tm["tokens"], peak_bytes=peak_bytes(),
           devices_holding_state=len(held),
           bytes_in_use_per_device=",".join(map(str, in_use)),
           max_logit_err_vs_one_chip=f"{errs['default']:.3e}",
           tol=MESH_LOGIT_TOL["default"],
           max_logit_err_vs_one_chip_highest=f"{errs['highest']:.3e}",
           tol_highest=MESH_LOGIT_TOL["highest"],
           bf16_control_err=f"{ctl:.3e}", fallbacks=fallbacks)
    check(len(held) == 4, f"engine state spans devices {sorted(held)}")
    for prec in PRECISIONS:
        check(errs[prec] <= MESH_LOGIT_TOL[prec],
              f"mesh: {prec} logit error {errs[prec]:.3e} > "
              f"{MESH_LOGIT_TOL[prec]}")
    return "mesh-olmoe-2x2"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the repo's own modules first: outside a checkout this fails before
    # JAX ever touches a device
    from repro.kernels import ops as kops
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    enable_compile_cache()
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    if kops.dispatch_key() != (True, False, False):
        print("chip_smoke: Pallas kernels are off or in interpret mode "
              f"(use_pallas, force_reference, interpret = "
              f"{kops.dispatch_key()})", file=sys.stderr)
        return 2
    report("device", platform=platform, kind=repr(devs[0].device_kind),
           count=len(devs), jax=jax.__version__)

    if args.chips == 4:
        mesh_phase(args.seed)
    else:
        from repro import configs
        from repro.core.types import P8_2, P16_2
        vocab = configs.get_config("smollm-360m").vocab
        prompts = make_requests(args.seed, vocab, 16)
        serve_phase("serve-p16", P16_2, prompts, args.seed)
        serve_phase("serve-p8", P8_2, prompts[:4], args.seed)
        train_phase(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
