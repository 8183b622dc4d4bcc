import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  Everything below is ordinary code.
"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell this builds ShapeDtypeStruct stand-ins for params, optimizer
state, batches and KV caches (no allocation), jits the train/prefill/decode
step with explicit in_shardings on the production mesh, compiles, and dumps
memory_analysis / cost_analysis / collective-bytes to JSON for the roofline
table (EXPERIMENTS.md §Dry-run, §Roofline).

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — fix the sharding rules, not the script.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.shapes import SHAPES, skip_reason
from repro.core.types import P16_2
from repro.distributed import sharding as sh
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import init_caches, init_params
from repro.optim import adamw
from repro.quant.policy import PositPolicy
from repro.quant.ptq import serving_param_specs
from repro.serving.engine import decode_step, prefill_step
from repro.training.train_step import train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# paper-mode posit policies
from repro.core.types import P8_2
TRAIN_POLICY = PositPolicy(weights=P16_2)                  # QAT posit16 weights
SERVE_POLICY = PositPolicy(weights=P16_2, kv_cache=P16_2)  # PTQ + posit KV
SERVE_POLICY_P8 = PositPolicy(weights=P8_2, kv_cache=P8_2)

# --format axis for the posit-vs-float comparison (§Perf iteration C):
#   p16 (default) / p8: posit policy;  bf16: bf16 act+KV, f32 weights;
#   f32: everything float32 — the paper's binary32 reference
FORMATS = ("p16", "p8", "bf16", "f32")


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def model_config(arch: str, shape, mode: str, fmt: str = "p16",
                 n_layers: int | None = None, scan_layers: bool = True):
    # production numerics: bf16 activations, f32 master weights (+posit
    # storage per policy); "f32" is the paper's binary32 reference
    over = {"dtype": "float32" if fmt == "f32" else "bfloat16",
            "scan_layers": scan_layers}
    if fmt == "p16":
        over["policy"] = SERVE_POLICY if mode != "train" else TRAIN_POLICY
    elif fmt == "p8":
        over["policy"] = (SERVE_POLICY_P8 if mode != "train"
                          else PositPolicy(weights=P8_2))
    cfg = configs.get_config(arch, **over)
    if n_layers is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def batch_specs(cfg, shape):
    B, S = shape.global_batch, shape.seq_len
    if cfg.encoder_only:
        return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.float32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    batch = {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
    if cfg.input_mode == "tokens+image":
        from repro.configs.phi_3_vision_4_2b import N_PATCHES
        batch["tokens"] = jax.ShapeDtypeStruct((B, S + 1 - N_PATCHES), jnp.int32)
        batch["image_embeds"] = jax.ShapeDtypeStruct(
            (B, N_PATCHES, cfg.d_model), jnp.float32)
    return batch


def build_cell(arch: str, shape, mesh, multi_pod: bool, fmt: str = "p16",
               n_layers: int | None = None, scan_layers: bool = True):
    """Returns (jitted_fn, arg_specs) ready to .lower(*arg_specs)."""
    mode = shape.kind
    cfg = model_config(arch, shape, mode, fmt, n_layers, scan_layers)
    B, S = shape.global_batch, shape.seq_len
    # serving is weight-stationary: TP sharding keeps the (huge) weights put
    # and moves only (B, 1/S_chunk, d) activations through psums — FSDP
    # weight gathers per decoded token are the §Perf iteration-B pathology
    strategy = "tp2d" if mode != "train" else sh.strategy_for(cfg, mesh)

    param_shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    pspec = sh.param_pspecs(param_shapes, mesh, multi_pod, strategy)
    psh = sh.to_shardings(pspec, mesh)

    if mode == "train":
        moment_dtype = ("bfloat16"
                        if cfg.param_count() > 5e10 else "float32")
        opt_cfg = adamw.OptConfig(moment_dtype=moment_dtype)
        opt_shapes = jax.eval_shape(
            lambda: adamw.init_state(param_shapes, opt_cfg))
        ospec = sh.opt_state_pspecs(opt_shapes, pspec, mesh)
        osh = sh.to_shardings(ospec, mesh)
        bspecs = batch_specs(cfg, shape)
        bspec = sh.batch_pspecs(bspecs, mesh, multi_pod,
                                shard_seq=(B < 16), strategy=strategy)
        bsh = sh.to_shardings(bspec, mesh)

        # >=50B models: 16-way gradient accumulation (activation temp /16,
        # same math — §Perf iteration A2)
        accum = 16 if cfg.param_count() > 5e10 else 1
        fn = jax.jit(
            lambda p, o, b: train_step(p, o, b, cfg, opt_cfg,
                                       accum_steps=accum),
            in_shardings=(psh, osh, bsh),
            donate_argnums=(0, 1))
        return fn, (param_shapes, opt_shapes, bspecs)

    # serving: PTQ posit weights
    if fmt in ("p16", "p8"):
        param_shapes = serving_param_specs(param_shapes,
                                           P16_2 if fmt == "p16" else P8_2)
        pspec = sh.param_pspecs(param_shapes, mesh, multi_pod, strategy)
        psh = sh.to_shardings(pspec, mesh)

    cache_shapes = jax.eval_shape(
        lambda: init_caches(cfg, B, S, dtype=jnp.dtype(cfg.dtype)))
    cspec = sh.cache_pspecs(cache_shapes, mesh, multi_pod, strategy)
    csh = sh.to_shardings(cspec, mesh)

    if mode == "prefill":
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.encoder_only:
            # encoder prefill == one full forward over embeddings
            from repro.models.transformer import forward
            emb = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.float32)
            espec = sh.batch_pspecs({"e": emb}, mesh, multi_pod,
                                    strategy=strategy)["e"]
            fn = jax.jit(
                lambda p, e: forward(p, cfg, inputs_embeds=e)[0],
                in_shardings=(psh, sh.to_shardings(espec, mesh)))
            return fn, (param_shapes, emb)
        if cfg.input_mode == "tokens+image":
            from repro.configs.phi_3_vision_4_2b import N_PATCHES
            tok = jax.ShapeDtypeStruct((B, S - N_PATCHES), jnp.int32)
        tspec = sh.batch_pspecs({"t": tok}, mesh, multi_pod,
                                strategy=strategy)["t"]
        fn = jax.jit(
            lambda p, t, c: prefill_step(p, cfg, t, c),
            in_shardings=(psh, sh.to_shardings(tspec, mesh), csh),
            donate_argnums=(2,))
        return fn, (param_shapes, tok, cache_shapes)

    # decode: cache filled to S-1, one new token
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tspec = sh.batch_pspecs({"t": tok}, mesh, multi_pod,
                            strategy=strategy)["t"]
    fn = jax.jit(
        lambda p, t, c: decode_step(p, cfg, t, c),
        in_shardings=(psh, sh.to_shardings(tspec, mesh), csh),
        donate_argnums=(2,))
    return fn, (param_shapes, tok, cache_shapes)


def _probe_counters(arch, shape, mesh, multi_pod, fmt, n_layers):
    """Compile an unrolled reduced-depth probe; return (flops, bytes, coll)."""
    fn, arg_specs = build_cell(arch, shape, mesh, multi_pod, fmt,
                               n_layers=n_layers, scan_layers=False)
    compiled = fn.lower(*arg_specs).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = analysis.parse_collectives(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            coll.total_bytes, coll.by_op)


def probe_roofline(arch, shape, mesh, multi_pod, fmt) -> dict:
    """Trip-count-correct roofline counters via linear extrapolation.

    XLA cost_analysis counts a scanned body once; we compile UNROLLED probes
    at L=P and L=2P layers (P = block-pattern length), solve
    outside = 2*c1 - c2, per_pattern = c2 - c1.  A hybrid remainder
    (recurrentgemma's trailing rglru pair: n_layers % P != 0) gets its own
    probe at L = P + rem, whose delta over c1 is exactly the remainder
    layers' cost:  total(L) = outside + (L // P) * per_pattern + rem_cost.
    Exact for every stack, uniform or hybrid.
    """
    cfg_full = configs.get_config(arch)
    P = len(cfg_full.block_pattern)
    c1 = _probe_counters(arch, shape, mesh, multi_pod, fmt, P)
    c2 = _probe_counters(arch, shape, mesh, multi_pod, fmt, 2 * P)
    reps, rem = divmod(cfg_full.n_layers, P)
    c3 = (_probe_counters(arch, shape, mesh, multi_pod, fmt, P + rem)
          if rem else None)
    out = {}
    names = ("flops_per_device", "bytes_per_device",
             "collective_bytes_per_device")
    for i, name in enumerate(names):
        outside = 2 * c1[i] - c2[i]
        per_pattern = c2[i] - c1[i]
        rem_cost = (c3[i] - c1[i]) if c3 is not None else 0.0
        out[name] = max(outside, 0.0) + reps * per_pattern + rem_cost
    out["probe_collectives_by_op_2p"] = c2[3]
    pk = analysis.peaks(analysis.TARGET_KIND)
    out["t_compute_s"] = out["flops_per_device"] / pk["flops_bf16"]
    out["t_memory_s"] = out["bytes_per_device"] / pk["hbm_bw"]
    out["t_collective_s"] = (out["collective_bytes_per_device"]
                             / pk["ici_bw"])
    out["bottleneck"] = max(
        ("compute", out["t_compute_s"]), ("memory", out["t_memory_s"]),
        ("collective", out["t_collective_s"]), key=lambda kv: kv[1])[0]
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fmt: str = "p16", save: bool = True) -> dict:
    shape = SHAPES[shape_name]
    cfg_plain = configs.get_config(arch)
    reason = skip_reason(cfg_plain, shape)
    mesh_name = "multipod" if multi_pod else "pod"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "posit": fmt in ("p16", "p8"), "format": fmt, "status": None}
    if reason:
        rec["status"] = "skip"
        rec["reason"] = reason
        print(f"[dryrun] SKIP {arch} x {shape_name}: {reason}")
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        t0 = time.time()
        try:
            strategy = ("tp2d" if shape.kind != "train"
                        else sh.strategy_for(configs.get_config(arch), mesh))
            rec["strategy"] = strategy
            with mesh, sh.activation_sharding(mesh, multi_pod, strategy):
                fn, arg_specs = build_cell(arch, shape, mesh, multi_pod, fmt)
                lowered = fn.lower(*arg_specs)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
                terms = analysis.roofline_terms(
                    compiled, mesh.size, analysis.peaks(analysis.TARGET_KIND))
                print(compiled.memory_analysis())
                # scan bodies are cost-counted once; probes fix trip counts
                terms.update(probe_roofline(arch, shape, mesh, multi_pod,
                                            fmt))
            rec.update(terms)
            rec["model_flops_analytic"] = analysis.model_flops(
                cfg_plain, shape, shape.kind == "decode")
            # per-layer serving-cache accounting from the backends' memory
            # descriptors: state layers are O(1)/seq, windowed KV
            # O(window), full KV O(context) — the exact bytes the paged
            # engine holds per sequence at this shape's context length
            from repro.serving.backends import layout_for
            layout = layout_for(cfg_plain)
            page = 64
            rec["serving_cache"] = {
                "page_size": page,
                "per_layer": [
                    {"kind": d.kind, "backend": d.backend,
                     "bytes_per_seq": d.bytes_per_seq(shape.seq_len, page)}
                    for d in layout.descs(page)],
                "bytes_per_seq": layout.cache_bytes_per_seq(shape.seq_len,
                                                            page),
            }
            rec["t_lower_s"] = round(t_lower, 1)
            rec["t_compile_s"] = round(t_compile, 1)
            rec["status"] = "ok"
            print(f"[dryrun] OK {arch} x {shape_name} x {mesh_name} "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s) "
                  f"bottleneck={terms['bottleneck']}")
        except Exception as e:
            rec["status"] = "fail"
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-4000:]
            print(f"[dryrun] FAIL {arch} x {shape_name} x {mesh_name}: "
                  f"{type(e).__name__}: {str(e)[:300]}")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_name}" + \
            ("" if fmt == "p16" else f"__{fmt}") + ".json"
        with open(os.path.join(RESULTS_DIR, fname), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-posit", action="store_true",
                    help="alias for --format bf16")
    ap.add_argument("--format", default="p16", choices=list(FORMATS))
    args = ap.parse_args()
    if args.no_posit:
        args.format = "bf16" 

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in configs.ARCHS:
            for sname in SHAPES:
                cells.append((arch, sname))
    else:
        cells.append((args.arch, args.shape))

    summary = []
    for arch, sname in cells:
        for mp in meshes:
            rec = run_cell(arch, sname, mp, fmt=args.format)
            summary.append((arch, sname, rec["status"]))
    n_ok = sum(1 for *_, s in summary if s == "ok")
    n_skip = sum(1 for *_, s in summary if s == "skip")
    n_fail = sum(1 for *_, s in summary if s == "fail")
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
