"""Production training launcher.

On a real TPU pod slice this is executed once per host:

    python -m repro.launch.train --arch smollm-360m --steps 1000 \
        --ckpt-dir gs://.../ckpts --mesh pod --restart-on-failure

On this CPU container it drives the same code path on a 1x1 mesh (used by
examples/ and the integration tests).  The mesh/sharding configuration is
identical to what launch/dryrun.py proves compiles for the production mesh.

Fault tolerance: --restart-on-failure hands the run to the elastic
process-group supervisor (launch/supervisor.py) — the trainer runs in
child processes that are *re-execed* on crash or straggler timeout,
resuming from the newest valid checkpoint (checkpoints every --ckpt-every
steps; the data pipeline is seekable); --workers N spawns an N-process
elastic data-parallel group (jax.distributed over localhost TCP) that
shrinks to the survivors on a worker death; --step-timeout arms the
supervisor's heartbeat straggler watchdog (process-level), or the
in-process fault_tolerance.StepWatchdog on the plain single-process path;
--async-ckpt moves checkpoint writes off the training thread
(checkpoint/async_store.py).

TPU compiler flags (latency-hiding overlap of the FSDP gathers —
DESIGN.md §5) are exported here so runs, and the supervisor's workers,
inherit them:
    --xla_tpu_enable_async_collective_fusion=true
    --xla_tpu_enable_latency_hiding_scheduler=true
    --xla_tpu_overlap_compute_collective_tc=true
They go through LIBTPU_INIT_ARGS, which libtpu parses and everything else
ignores: libtpu 0.0.34 accepts all three there, while XLA_FLAGS aborts the
process on them ("Unknown flag in XLA_FLAGS").
"""
from __future__ import annotations

import argparse
import os


TPU_LIBTPU_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_overlap_compute_collective_tc=true"
)


def run_configs(arch: str, *, steps: int, seq_len: int, global_batch: int,
                lr: float, posit: str, smoke: bool = False):
    """(model, optimizer, data) configs of one training run: posit STE
    weights per `posit` (off | p8 | p16), warmup over the first tenth of
    the steps (at most 100).  main() and chip_smoke.py both build runs
    here."""
    from repro import configs
    from repro.core.types import P8_2, P16_2
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import OptConfig
    from repro.quant.policy import PositPolicy

    policy = {"off": PositPolicy(),
              "p8": PositPolicy(weights=P8_2),
              "p16": PositPolicy(weights=P16_2)}[posit]
    get = configs.get_smoke if smoke else configs.get_config
    cfg = get(arch, policy=policy)
    opt_cfg = OptConfig(lr_peak=lr, warmup_steps=min(100, steps // 10 + 1),
                        total_steps=steps)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                          global_batch=global_batch)
    return cfg, opt_cfg, data_cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--posit", choices=["off", "p8", "p16"], default="p16")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis (1 = single device)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh axis (attention/MLP stacks)")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N CPU host devices (sets XLA_FLAGS; must "
                         "run before jax initializes)")
    ap.add_argument("--restart-on-failure", action="store_true")
    ap.add_argument("--max-restarts", type=int, default=10)
    ap.add_argument("--step-timeout", type=float, default=None)
    ap.add_argument("--workers", type=int, default=1,
                    help="elastic data-parallel worker processes "
                         "(>1 implies the supervisor path)")
    ap.add_argument("--min-workers", type=int, default=1)
    ap.add_argument("--async-ckpt", action="store_true",
                    help="background checkpoint writes (bounded queue)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # ahead of the inherited value: libtpu keeps the last of a repeated flag
    os.environ["LIBTPU_INIT_ARGS"] = (
        TPU_LIBTPU_FLAGS + " "
        + os.environ.get("LIBTPU_INIT_ARGS", "")).strip()

    if args.workers > 1 or args.restart_on_failure:
        # elastic supervisor path: the trainer runs in child processes
        # that are re-execed (and the group shrunk) on failure
        if not args.ckpt_dir:
            ap.error("--restart-on-failure/--workers>1 need --ckpt-dir "
                     "(restarts resume from it)")
        from repro.distributed.fault_tolerance import RestartPolicy
        from repro.launch.supervisor import supervise_training
        policy = RestartPolicy(ckpt_every=args.ckpt_every,
                               max_restarts=args.max_restarts,
                               step_timeout_s=args.step_timeout,
                               min_workers=args.min_workers)
        out = supervise_training(
            args.arch, args.steps, args.ckpt_dir,
            os.path.join(args.ckpt_dir, "run"), workers=args.workers,
            policy=policy, global_batch=args.global_batch,
            seq_len=args.seq_len, lr=args.lr, seed=args.seed,
            smoke=args.smoke, async_ckpt=args.async_ckpt, posit=args.posit)
        print(f"[launch] supervisor outcome: {out.status} "
              f"({out.restarts} restart(s), {out.final_workers} final "
              f"worker(s))" + (f" — {out.error}" if out.error else ""))
        raise SystemExit(0 if out.ok else 1)

    if args.host_devices:
        # append (not prepend): XLA applies the *last* duplicate flag, so an
        # inherited force_host_platform_device_count must not win over the
        # explicit request
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.host_devices}")

    from repro.distributed.fault_tolerance import RestartPolicy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.training.trainer import train_loop

    enable_compile_cache()
    cfg, opt_cfg, data_cfg = run_configs(
        args.arch, steps=args.steps, seq_len=args.seq_len,
        global_batch=args.global_batch, lr=args.lr, posit=args.posit,
        smoke=args.smoke)
    rp = RestartPolicy(ckpt_every=args.ckpt_every,
                       step_timeout_s=args.step_timeout)

    mesh = None
    if args.dp > 1 or args.tp > 1:
        # same builder as sharded serving: whatever jax.devices() offers (a
        # TPU slice, or XLA_FLAGS=--xla_force_host_platform_device_count=N)
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(data=args.dp, model=args.tp)

    train_loop(cfg, opt_cfg, data_cfg, args.steps,
               ckpt_dir=args.ckpt_dir, policy=rp, mesh=mesh,
               accum_steps=args.accum_steps, seed=args.seed,
               async_ckpt=args.async_ckpt)


if __name__ == "__main__":
    main()
