"""Elastic process-group supervisor: restart-on-failure that actually
re-execs processes, not a try/except around the train loop.

    python -m repro.launch.supervisor --arch smollm-360m --smoke \
        --workers 4 --steps 200 --ckpt-dir /tmp/ck --step-timeout 60

The supervisor spawns N worker processes (jax.distributed.initialize over
localhost TCP — gloo CPU collectives, the same subprocess pattern as
tests/test_serving_sharded.py), and watches two signals:

  * process exit codes — a worker killed by a signal (rc < 0) is a node
    death; rc == COLLATERAL_RC (75) is a worker that died *because a peer
    vanished mid-collective* and must not count as its own failure;
  * per-worker heartbeat files (fault_tolerance.Heartbeat: step + phase +
    timestamp, atomically renamed) — a heartbeat stale past
    --step-timeout is a straggler even though the process is alive, and
    no heartbeat within startup_timeout_s is a hung launch.

On any failure it kills the whole group (SIGTERM, then SIGKILL), backs
off exponentially (RestartPolicy.backoff_s * 2**n, capped), and re-execs
with the data axis shrunk to the survivors — crashed/straggling workers
are removed; collateral deaths and clean exits are not.  Restarts are
bounded by RestartPolicy.max_restarts and floored at min_workers; the
run ends in a structured RunOutcome (completed | exhausted_restarts |
failed), never an unhandled exception.

The shrunk group resumes from the newest valid checkpoint and — because
per-host batches are derived (data.pipeline.host_batch_at) and gradient
reduction is regroup-invariant (training/elastic.py) — produces
parameters bit-identical to an uninterrupted run.  tests/test_supervisor.py
pins exactly that: SIGKILL one of 4 workers mid-run, compare final
params against a same-seed single-process run.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import os
import signal
import socket
import subprocess
import sys
import time

from repro.distributed.fault_tolerance import (PHASE_RANK, RestartPolicy,
                                               read_heartbeat)

# a worker that dies because a *peer* vanished mid-collective exits with
# this code; the supervisor restarts but does not shrink it away
COLLATERAL_RC = 75


@dataclasses.dataclass
class GenRecord:
    """One generation (spawn) of the worker group, for the bench/tests."""
    gen: int
    workers: int
    started_t: float
    ended_t: float = 0.0
    first_step: int | None = None   # min heartbeat step seen this gen
    last_step: int | None = None    # max heartbeat step seen this gen
    failure: str | None = None      # crash | straggler | startup_timeout |
                                    # collateral | error | None (completed)
    culprits: tuple[int, ...] = ()  # host_ids removed going into next gen


@dataclasses.dataclass
class RunOutcome:
    status: str                     # completed | exhausted_restarts | failed
    restarts: int
    final_workers: int
    generations: list[GenRecord]
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.status == "completed"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _kill_group(procs, grace_s: float = 5.0):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.monotonic() + grace_s
    for p in procs:
        while p.poll() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        if p.poll() is None:
            p.kill()
            p.wait()


def supervise(make_cmd, workers: int, policy: RestartPolicy, run_dir: str,
              *, env: dict | None = None, poll_s: float = 0.2,
              verbose: bool = True) -> RunOutcome:
    """Generic supervisor loop, decoupled from jax so tests can drive it
    with toy workers.

    make_cmd(gen, host_id, num_hosts, port, hb_path) -> argv for one
    worker.  host_id here is the *dense rank within the generation*; the
    worker itself decides what to do with it (the training worker derives
    its batch slice from it).  Heartbeats land in
    <run_dir>/gen<g>/hb_<rank>.json, worker output in
    <run_dir>/gen<g>/worker_<rank>.log.
    """
    os.makedirs(run_dir, exist_ok=True)
    outcome = RunOutcome("failed", 0, workers, [])
    gen = 0
    while True:
        if workers < policy.min_workers:
            outcome.status = "failed"
            outcome.error = (f"{workers} worker(s) left, below "
                             f"min_workers={policy.min_workers}")
            return outcome
        gen_dir = os.path.join(run_dir, f"gen{gen}")
        os.makedirs(gen_dir, exist_ok=True)
        port = _free_port()
        hb_paths = [os.path.join(gen_dir, f"hb_{r}.json")
                    for r in range(workers)]
        rec = GenRecord(gen, workers, time.time())
        outcome.generations.append(rec)
        outcome.final_workers = workers
        procs, logs = [], []
        for r in range(workers):
            log = open(os.path.join(gen_dir, f"worker_{r}.log"), "wb")
            logs.append(log)
            procs.append(subprocess.Popen(
                make_cmd(gen, r, workers, port, hb_paths[r]),
                stdout=log, stderr=subprocess.STDOUT, env=env))
        if verbose:
            print(f"[supervisor] gen {gen}: {workers} worker(s), "
                  f"port {port}", flush=True)

        failure, culprits = _monitor(procs, hb_paths, policy, poll_s, rec)
        _kill_group(procs)
        rec.ended_t = time.time()
        rec.failure = failure
        rec.culprits = tuple(culprits)
        for log in logs:
            log.close()

        if failure is None:
            outcome.status = "completed"
            return outcome
        if verbose:
            print(f"[supervisor] gen {gen} failed: {failure} "
                  f"(culprit ranks {sorted(culprits)}); "
                  f"last step {rec.last_step}", flush=True)
        outcome.restarts += 1
        if outcome.restarts > policy.max_restarts:
            outcome.status = "exhausted_restarts"
            outcome.error = f"gave up after {policy.max_restarts} restarts"
            return outcome
        # shrink only for failures attributable to specific workers; a
        # collateral-only generation (everyone exited 75 — e.g. the
        # coordinator hiccuped) restarts at the same size
        if failure in ("crash", "straggler", "startup_timeout"):
            workers -= len(culprits)
        backoff = min(policy.backoff_s * 2 ** (outcome.restarts - 1),
                      policy.backoff_max_s)
        time.sleep(backoff)
        gen += 1


def _monitor(procs, hb_paths, policy: RestartPolicy, poll_s: float,
             rec: GenRecord):
    """Watch one generation.  Returns (failure, culprit_ranks);
    failure None means every worker exited 0."""
    n = len(procs)
    start = time.monotonic()
    while True:
        time.sleep(poll_s)
        now = time.time()
        beats = [read_heartbeat(p) for p in hb_paths]
        steps = [b["step"] for b in beats if b]
        if steps:
            rec.first_step = (min(steps) if rec.first_step is None
                              else min(rec.first_step, min(steps)))
            rec.last_step = (max(steps) if rec.last_step is None
                             else max(rec.last_step, max(steps)))

        rcs = [p.poll() for p in procs]
        crashed = [r for r, rc in enumerate(rcs)
                   if rc is not None and rc < 0]
        if crashed:
            return "crash", crashed
        errored = [r for r, rc in enumerate(rcs)
                   if rc is not None and rc not in (0, COLLATERAL_RC)]
        if errored:
            # deterministic worker bug: removing it won't help, restart
            # same-size and let max_restarts bound the loop
            return "error", errored
        if all(rc is not None for rc in rcs):
            if all(rc == 0 for rc in rcs):
                return None, []
            return "collateral", []     # only rc==75 deaths: peer fallout

        # liveness: startup deadline before the first beat, straggler
        # deadline after.  A straggler stalls its peers inside the
        # exchange collective, so *all* heartbeats go stale — the
        # culprit is the worker stuck at the earliest (step, phase):
        # everyone else already advanced to the sync phase and is merely
        # blocked waiting for it.
        alive = [r for r, rc in enumerate(rcs) if rc is None]
        hung = [r for r in alive if beats[r] is None
                and time.monotonic() - start > policy.startup_timeout_s]
        if hung:
            return "startup_timeout", hung
        if policy.step_timeout_s:
            stale = [r for r in alive if beats[r]
                     and beats[r]["phase"] != "done"
                     and now - beats[r]["t"] > policy.step_timeout_s]
            if stale:
                key = lambda r: (beats[r]["step"],
                                 PHASE_RANK[beats[r]["phase"]])
                worst = min(key(r) for r in stale)
                return "straggler", [r for r in stale if key(r) == worst]


# --------------------------------------------------------------------------
# the training worker group
# --------------------------------------------------------------------------

def tpu_chips_on_host() -> int:
    """TPU chips this host exposes, counted from their device nodes
    (/dev/accel<n>, or /dev/vfio/<n> on generations that pass chips through
    VFIO); 0 when JAX_PLATFORMS pins the CPU.  No JAX here: on a TPU host a
    process that initializes JAX holds every chip, so the parent never
    does."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return 0
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def _worker_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # forced host-device counts break gloo init
    env["PYTHONUNBUFFERED"] = "1"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def supervise_training(arch: str, steps: int, ckpt_dir: str, run_dir: str, *,
                       workers: int = 1, policy: RestartPolicy | None = None,
                       global_batch: int = 8, seq_len: int = 128,
                       lr: float = 3e-4, seed: int = 0, smoke: bool = False,
                       async_ckpt: bool = False, posit: str = "p16",
                       chaos_kill: str | None = None,
                       chaos_straggle: str | None = None,
                       verbose: bool = True) -> RunOutcome:
    """Supervise an elastic training group (the CLI below and
    launch/train.py both land here).  chaos_kill="host:step" /
    chaos_straggle="host:step:seconds" inject a fault into generation 0
    only — restarted generations run clean, which is what lets the tests
    assert recovery."""
    policy = policy or RestartPolicy()
    chips = tpu_chips_on_host()
    if chips and workers > 1:
        # every worker would initialize the TPU backend and claim all the
        # chips: the second one fails on libtpu's lock or hangs.  The
        # group's gradient exchange runs over gloo on the host, so there is
        # no per-chip layout to hand out yet.
        raise ValueError(
            f"--workers {workers} on a TPU host ({chips} chip(s)): a worker "
            f"process holds every chip it can see, so the elastic group "
            f"runs one worker per TPU host; use --workers 1")
    env = _worker_env()

    def make_cmd(gen, host_id, num_hosts, port, hb_path):
        cmd = [sys.executable, "-m", "repro.launch.supervisor", "--worker",
               "--arch", arch, "--steps", str(steps),
               "--ckpt-dir", ckpt_dir, "--heartbeat", hb_path,
               "--host-id", str(host_id), "--num-hosts", str(num_hosts),
               "--port", str(port), "--gen", str(gen),
               "--global-batch", str(global_batch),
               "--seq-len", str(seq_len), "--lr", str(lr),
               "--seed", str(seed), "--posit", posit,
               "--ckpt-every", str(policy.ckpt_every),
               "--keep", str(policy.keep)]
        if smoke:
            cmd.append("--smoke")
        if async_ckpt:
            cmd.append("--async-ckpt")
        if gen == 0:
            if chaos_kill:
                cmd += ["--chaos-kill", chaos_kill]
            if chaos_straggle:
                cmd += ["--chaos-straggle", chaos_straggle]
        return cmd

    return supervise(make_cmd, workers, policy, run_dir, env=env,
                     verbose=verbose)


def _parse_chaos(spec: str | None, parts: int):
    if not spec:
        return None
    vals = spec.split(":")
    if len(vals) != parts:
        raise ValueError(f"bad chaos spec {spec!r}")
    return tuple(float(v) if i == 2 else int(v) for i, v in enumerate(vals))


def _resolve_cfg(arch: str, smoke: bool, posit: str):
    if arch == "tiny":    # the chaos-suite workload: seconds per generation
        from repro.models.transformer import ModelConfig
        return ModelConfig("tiny", n_layers=2, d_model=64, n_heads=4,
                           n_kv=2, d_ff=128, vocab=128)
    from repro import configs
    from repro.core.types import P8_2, P16_2
    from repro.quant.policy import PositPolicy
    pol = {"off": PositPolicy(), "p8": PositPolicy(weights=P8_2),
           "p16": PositPolicy(weights=P16_2)}[posit]
    get = configs.get_smoke if smoke else configs.get_config
    return get(arch, policy=pol)


def _worker_main(args):
    """One member of the elastic group (invoked with --worker)."""
    if args.num_hosts > 1:
        import jax
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=f"localhost:{args.port}",
            num_processes=args.num_hosts, process_id=args.host_id)

    from repro.data.pipeline import DataConfig
    from repro.distributed.fault_tolerance import Heartbeat
    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim.adamw import OptConfig
    from repro.training.elastic import elastic_train_loop

    enable_compile_cache()

    cfg = _resolve_cfg(args.arch, args.smoke, args.posit)
    opt_cfg = OptConfig(lr_peak=args.lr,
                        warmup_steps=min(100, args.steps // 10 + 1),
                        total_steps=args.steps)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed)
    policy = RestartPolicy(ckpt_every=args.ckpt_every, keep=args.keep)
    hb = Heartbeat(args.heartbeat, args.host_id) if args.heartbeat else None

    kill = _parse_chaos(args.chaos_kill, 2)
    strag = _parse_chaos(args.chaos_straggle, 3)
    kwargs = {}
    if kill and kill[0] == args.host_id:
        kwargs["chaos_kill_at"] = int(kill[1])
    if strag and strag[0] == args.host_id:
        kwargs["chaos_straggle_at"] = int(strag[1])
        kwargs["chaos_straggle_s"] = strag[2]

    try:
        elastic_train_loop(cfg, opt_cfg, data_cfg, args.steps,
                           ckpt_dir=args.ckpt_dir, policy=policy,
                           host_id=args.host_id, num_hosts=args.num_hosts,
                           heartbeat=hb, async_ckpt=args.async_ckpt,
                           seed=args.seed, **kwargs)
    except Exception as e:
        # in a multi-host group, an exchange/collective error here is very
        # likely fallout from a dead peer — exit COLLATERAL_RC so the
        # supervisor restarts without shrinking this worker away
        print(f"[worker {args.host_id}] {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        sys.exit(COLLATERAL_RC if args.num_hosts > 1 else 1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--run-dir", default=None,
                    help="heartbeats + worker logs (default <ckpt>/run)")
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--posit", choices=["off", "p8", "p16"], default="p16")
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--max-restarts", type=int, default=10)
    ap.add_argument("--min-workers", type=int, default=1)
    ap.add_argument("--step-timeout", type=float, default=None)
    ap.add_argument("--startup-timeout", type=float, default=300.0)
    ap.add_argument("--chaos-kill", default=None, metavar="HOST:STEP")
    ap.add_argument("--chaos-straggle", default=None,
                    metavar="HOST:STEP:SECONDS")
    # worker-only plumbing
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--gen", type=int, default=0)
    ap.add_argument("--heartbeat", default=None)
    args = ap.parse_args(argv)

    if args.worker:
        _worker_main(args)
        return None

    policy = RestartPolicy(ckpt_every=args.ckpt_every, keep=args.keep,
                           max_restarts=args.max_restarts,
                           step_timeout_s=args.step_timeout,
                           min_workers=args.min_workers,
                           startup_timeout_s=args.startup_timeout)
    out = supervise_training(
        args.arch, args.steps, args.ckpt_dir,
        args.run_dir or os.path.join(args.ckpt_dir, "run"),
        workers=args.workers, policy=policy,
        global_batch=args.global_batch, seq_len=args.seq_len, lr=args.lr,
        seed=args.seed, smoke=args.smoke, async_ckpt=args.async_ckpt,
        posit=args.posit, chaos_kill=args.chaos_kill,
        chaos_straggle=args.chaos_straggle)
    print(f"[supervisor] {out.status}: {out.restarts} restart(s), "
          f"{out.final_workers} final worker(s), "
          f"{len(out.generations)} generation(s)"
          + (f" — {out.error}" if out.error else ""), flush=True)
    if not out.ok:
        sys.exit(1)
    return out


if __name__ == "__main__":
    main()
