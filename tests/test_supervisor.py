"""Elastic process-group supervisor (launch/supervisor.py).

Two layers:

  * toy-worker tests drive supervise() with tiny non-jax python workers
    (seconds each): outcome taxonomy, shrink-on-crash, straggler culprit
    selection by (step, phase), collateral rc=75 no-shrink, startup
    timeout, min_workers floor, restart exhaustion;
  * full-stack tests (slow, nightly elastic lane) run real
    jax.distributed training groups and pin the ISSUE acceptance row:
    SIGKILL 1 of 4 workers mid-run -> the supervisor restarts with 3 and
    the final params are bit-identical to an uninterrupted same-seed run;
    an induced straggler (sleep > --step-timeout) takes the same path.
"""
from __future__ import annotations

import os
import sys
import textwrap

import numpy as np
import pytest

from repro.distributed.fault_tolerance import RestartPolicy
from repro.launch.supervisor import COLLATERAL_RC, supervise

# ---------------------------------------------------------------------------
# toy workers: behaviour scripted per (gen, rank), heartbeats hand-written
# ---------------------------------------------------------------------------

_TOY = textwrap.dedent("""
    import json, os, sys, time
    hb_path, host_id, gen, mode = (sys.argv[1], int(sys.argv[2]),
                                   int(sys.argv[3]), sys.argv[4])
    def beat(step, phase="step"):
        tmp = hb_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"host_id": host_id, "step": step, "phase": phase,
                       "t": time.time()}, f)
        os.replace(tmp, hb_path)
    if mode == "no_beat":
        time.sleep(60)
    for step in range(4):
        beat(step)
        if mode == "crash" and step == 2:
            os.kill(os.getpid(), 9)
        if mode == "stall_step" and step == 2:
            time.sleep(60)
        if mode == "stall_sync" and step == 2:
            beat(step, "sync")
            time.sleep(60)
        if mode == "exit_err" and step == 2:
            sys.exit(7)
        if mode == "exit_collateral" and step == 2:
            sys.exit(75)
        time.sleep(0.05)
    beat(4, "done")
""")


def _toy_cmd(modes_by_gen_rank):
    """make_cmd for supervise(): modes_by_gen_rank[(gen, rank)] -> mode
    string, default 'ok'."""
    def make_cmd(gen, rank, num_hosts, port, hb_path):
        mode = modes_by_gen_rank.get((gen, rank), "ok")
        return [sys.executable, "-c", _TOY, hb_path, str(rank), str(gen),
                mode]
    return make_cmd


_FAST = dict(backoff_s=0.05, backoff_max_s=0.1, startup_timeout_s=10.0)


def test_supervisor_completed(tmp_path):
    out = supervise(_toy_cmd({}), 2, RestartPolicy(**_FAST),
                    str(tmp_path), verbose=False)
    assert out.status == "completed" and out.ok
    assert out.restarts == 0 and out.final_workers == 2
    assert [g.failure for g in out.generations] == [None]
    assert out.generations[0].last_step == 4


def test_supervisor_shrinks_on_crash(tmp_path):
    out = supervise(_toy_cmd({(0, 1): "crash"}), 3, RestartPolicy(**_FAST),
                    str(tmp_path), verbose=False)
    assert out.status == "completed"
    assert out.restarts == 1 and out.final_workers == 2
    g0, g1 = out.generations
    assert g0.failure == "crash" and g0.culprits == (1,)
    assert g1.workers == 2 and g1.failure is None


def test_supervisor_straggler_culprit_by_phase(tmp_path):
    # rank 0 stuck at (2, step); rank 1 reached (2, sync) and is "blocked
    # on the exchange": both heartbeats go stale, but only rank 0 — the
    # earliest (step, phase) — is the straggler to remove
    out = supervise(
        _toy_cmd({(0, 0): "stall_step", (0, 1): "stall_sync"}), 2,
        RestartPolicy(step_timeout_s=1.0, **_FAST),
        str(tmp_path), verbose=False)
    assert out.status == "completed"
    g0 = out.generations[0]
    assert g0.failure == "straggler" and g0.culprits == (0,)
    assert out.final_workers == 1


def test_supervisor_collateral_does_not_shrink(tmp_path):
    # gen 0: both workers exit COLLATERAL_RC (coordinator hiccup) — the
    # restart keeps the group at full size
    out = supervise(
        _toy_cmd({(0, 0): "exit_collateral", (0, 1): "exit_collateral"}),
        2, RestartPolicy(**_FAST), str(tmp_path), verbose=False)
    assert out.status == "completed"
    assert out.restarts == 1 and out.final_workers == 2
    assert out.generations[0].failure == "collateral"
    assert out.generations[0].culprits == ()


def test_supervisor_error_restarts_same_size_until_exhausted(tmp_path):
    # a deterministic worker bug (rc=7) restarts without shrinking and is
    # bounded by max_restarts
    out = supervise(
        _toy_cmd({(g, 0): "exit_err" for g in range(5)}), 2,
        RestartPolicy(max_restarts=2, **_FAST), str(tmp_path),
        verbose=False)
    assert out.status == "exhausted_restarts"
    assert out.restarts == 3 and out.final_workers == 2
    assert all(g.failure == "error" for g in out.generations)


def test_supervisor_min_workers_floor(tmp_path):
    out = supervise(
        _toy_cmd({(g, r): "crash" for g in range(4) for r in range(3)}), 2,
        RestartPolicy(min_workers=2, **_FAST), str(tmp_path),
        verbose=False)
    assert out.status == "failed"
    assert "min_workers" in out.error


def test_supervisor_startup_timeout(tmp_path):
    out = supervise(
        _toy_cmd({(0, 1): "no_beat"}), 2,
        RestartPolicy(**dict(_FAST, startup_timeout_s=1.0)),
        str(tmp_path), verbose=False)
    assert out.status == "completed"
    g0 = out.generations[0]
    assert g0.failure == "startup_timeout" and g0.culprits == (1,)
    assert out.final_workers == 1


@pytest.mark.parametrize("chips,workers,refused",
                         [(1, 2, True), (4, 4, True), (4, 1, False),
                          (0, 4, False)])
def test_training_group_on_tpu_host_one_worker(tmp_path, monkeypatch, chips,
                                               workers, refused):
    """On a TPU host every worker would claim all the chips, so a group of
    more than one worker is refused up front instead of hanging; a single
    worker (or a CPU host) reaches the spawn."""
    from repro.launch import supervisor as sup
    monkeypatch.setattr(sup, "tpu_chips_on_host", lambda: chips)
    spawned = []
    monkeypatch.setattr(sup, "supervise",
                        lambda make_cmd, n, *a, **k: spawned.append(n))
    if refused:
        with pytest.raises(ValueError, match="one worker per TPU host"):
            sup.supervise_training("tiny", 1, str(tmp_path), str(tmp_path),
                                   workers=workers, verbose=False)
        assert not spawned
    else:
        sup.supervise_training("tiny", 1, str(tmp_path), str(tmp_path),
                               workers=workers, verbose=False)
        assert spawned == [workers]


def test_tpu_chips_on_host_zero_when_cpu_pinned(monkeypatch):
    from repro.launch.supervisor import tpu_chips_on_host
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tpu_chips_on_host() == 0


# ---------------------------------------------------------------------------
# full stack: real jax.distributed training groups (nightly elastic lane)
# ---------------------------------------------------------------------------

STEPS, BATCH, SEQ = 6, 4, 32


def _reference_params(steps=STEPS):
    """Uninterrupted single-process run of the same seed/config."""
    from repro.data.pipeline import DataConfig
    from repro.models.transformer import ModelConfig
    from repro.optim.adamw import OptConfig
    from repro.training.elastic import elastic_train_loop
    cfg = ModelConfig("tiny", n_layers=2, d_model=64, n_heads=4, n_kv=2,
                      d_ff=128, vocab=128)
    opt_cfg = OptConfig(lr_peak=3e-4, warmup_steps=min(100, steps // 10 + 1),
                        total_steps=steps)
    data_cfg = DataConfig(vocab=128, seq_len=SEQ, global_batch=BATCH, seed=0)
    params, opt, _ = elastic_train_loop(cfg, opt_cfg, data_cfg, steps,
                                        verbose=False)
    return params, opt


def _final_params(ckpt_dir, example):
    from repro.checkpoint import store
    step, restored = store.restore_latest(ckpt_dir, example)
    assert step == STEPS, f"final checkpoint at step {step}, want {STEPS}"
    return restored["params"]


def _assert_bit_identical(ref, got):
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_supervised_kill_resumes_bit_identical(tmp_path):
    """The acceptance row: SIGKILL 1 of 4 workers mid-run — the
    supervisor restarts with 3 survivors and the final params match an
    uninterrupted same-seed run bit-for-bit."""
    from repro.launch.supervisor import supervise_training
    ck = str(tmp_path / "ck")
    out = supervise_training(
        "tiny", STEPS, ck, str(tmp_path / "run"), workers=4,
        policy=RestartPolicy(ckpt_every=2, step_timeout_s=180,
                             backoff_s=0.1),
        global_batch=BATCH, seq_len=SEQ, seed=0,
        chaos_kill="2:3", verbose=False)
    assert out.status == "completed", (out.status, out.error)
    assert out.restarts == 1 and out.final_workers == 3
    assert out.generations[0].failure == "crash"

    ref_params, ref_opt = _reference_params()
    got = _final_params(ck, {"params": ref_params, "opt": ref_opt})
    _assert_bit_identical(ref_params, got)


@pytest.mark.slow
def test_supervised_straggler_resumes_bit_identical(tmp_path):
    """An induced straggler (sleep > step-timeout) takes the same
    kill-group/shrink/resume path as a crash."""
    from repro.launch.supervisor import supervise_training
    ck = str(tmp_path / "ck")
    out = supervise_training(
        "tiny", STEPS, ck, str(tmp_path / "run"), workers=3,
        policy=RestartPolicy(ckpt_every=2, step_timeout_s=20,
                             backoff_s=0.1),
        global_batch=BATCH, seq_len=SEQ, seed=0,
        chaos_straggle="1:3:600", verbose=False)
    assert out.status == "completed", (out.status, out.error)
    assert out.restarts == 1 and out.final_workers == 2
    assert out.generations[0].failure == "straggler"

    ref_params, ref_opt = _reference_params()
    got = _final_params(ck, {"params": ref_params, "opt": ref_opt})
    _assert_bit_identical(ref_params, got)


@pytest.mark.slow
def test_supervised_async_ckpt_group(tmp_path):
    """--async-ckpt through the whole supervised path still yields the
    bit-identical final checkpoint."""
    from repro.launch.supervisor import supervise_training
    ck = str(tmp_path / "ck")
    out = supervise_training(
        "tiny", STEPS, ck, str(tmp_path / "run"), workers=2,
        policy=RestartPolicy(ckpt_every=2, step_timeout_s=180,
                             backoff_s=0.1),
        global_batch=BATCH, seq_len=SEQ, seed=0, async_ckpt=True,
        verbose=False)
    assert out.status == "completed", (out.status, out.error)
    ref_params, ref_opt = _reference_params()
    got = _final_params(ck, {"params": ref_params, "opt": ref_opt})
    _assert_bit_identical(ref_params, got)
