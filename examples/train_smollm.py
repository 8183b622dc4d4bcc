"""End-to-end driver: train a smollm-family LM on the Pallas training
kernels twice — posit16 QAT weights vs the binary32 baseline — and emit a
loss-curve parity artifact (the LM-scale version of the paper's Fig. 7
"posits match binary32" experiment, now through the full kernel surface:
flash fwd/bwd, posit GEMM custom_vjp, donated train step).

Both legs run the *same* kernel path (REPRO_USE_PALLAS; interpret mode on
CPU), the same data stream and the same init seed, so the only difference
is the posit16 STE weight quantization.  The artifact records both loss
curves plus the gap statistics and the per-leg fallback counters (which
must stay empty — the zero-BWD_FALLBACKS training invariant).

Run:  PYTHONPATH=src python examples/train_smollm.py [--steps 80]
Writes experiments/smollm_p16_parity.json.
"""
import argparse
import json
import os

import jax

from repro.core.types import P16_2
from repro.kernels import ops as kops
from repro.data.pipeline import DataConfig
from repro.models.transformer import ModelConfig, init_params
from repro.optim.adamw import OptConfig
from repro.quant.policy import PositPolicy
from repro.training.trainer import train_loop

ARTIFACT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "smollm_p16_parity.json")


def run_leg(posit: bool, steps: int, log_every: int):
    # ~M-scale smollm-family config sized for interpret-mode CPU steps;
    # same code path as the 256-chip launch (launch/train.py).  Distinct
    # names: each leg jits its own step.
    cfg = ModelConfig(
        f"smollm-mini-{'p16' if posit else 'f32'}",
        n_layers=4, d_model=128, n_heads=8, n_kv=4, d_ff=384, vocab=1024,
        policy=PositPolicy(weights=P16_2) if posit else PositPolicy())
    opt = OptConfig(lr_peak=3e-3, warmup_steps=max(steps // 10, 5),
                    total_steps=steps)
    data = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    _, _, hist = train_loop(cfg, opt, data, steps, log_every=log_every,
                            verbose=True)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        init_params(jax.random.PRNGKey(0), cfg)))
    return cfg, n_params, hist


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    # the kernel path everywhere; off a TPU (decided by the backend JAX
    # initialized, not by JAX_PLATFORMS) the kernels run interpreted
    os.environ.setdefault("REPRO_USE_PALLAS", "1")
    if jax.default_backend() != "tpu":
        os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")

    legs = {}
    for name, posit in (("p16", True), ("f32", False)):
        print(f"[example] === {name} leg "
              f"({'posit16 QAT weights' if posit else 'binary32'}) ===")
        cfg, n_params, hist = run_leg(posit, args.steps, args.log_every)
        fallbacks = {}
        for row in hist:
            for k, v in row.get("fallbacks", {}).items():
                fallbacks[k] = fallbacks.get(k, 0) + v
        legs[name] = {
            "arch": cfg.name,
            "params_m": round(n_params / 1e6, 2),
            "curve": [{"step": r["step"], "loss": round(r["loss"], 4)}
                      for r in hist],
            "final_loss": round(hist[-1]["loss"], 4),
            "steps_per_s": round(hist[-1]["steps_per_s"], 3),
            "bwd_fallbacks": fallbacks,
        }
        print(f"[example] {name}: loss {hist[0]['loss']:.3f} -> "
              f"{hist[-1]['loss']:.3f} over {args.steps} steps")

    gaps = [abs(a["loss"] - b["loss"])
            for a, b in zip(legs["p16"]["curve"], legs["f32"]["curve"])]
    res = {
        "experiment": "posit16 QAT vs binary32 loss parity, kernel path "
                      "(flash fwd/bwd + posit GEMM custom_vjp + donated "
                      "train step)",
        "backend": jax.default_backend(),
        "interpret": kops.pallas_interpret(),
        "steps": args.steps,
        "seq_len": 64, "global_batch": 8,
        "p16": legs["p16"], "f32": legs["f32"],
        "loss_gap_final": round(
            abs(legs["p16"]["final_loss"] - legs["f32"]["final_loss"]), 4),
        "loss_gap_max": round(max(gaps), 4),
    }
    os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
    with open(ARTIFACT, "w") as f:
        json.dump(res, f, indent=1)
    print(f"[example] wrote {os.path.normpath(ARTIFACT)}: "
          f"final p16 {legs['p16']['final_loss']} vs "
          f"f32 {legs['f32']['final_loss']} "
          f"(gap {res['loss_gap_final']})")


if __name__ == "__main__":
    main()
