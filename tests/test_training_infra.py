"""Trainer, checkpointing, fault tolerance, data pipeline, collectives."""
import glob
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import store
from repro.data.pipeline import DataConfig, global_batch_at, host_batch_at
from repro.distributed.fault_tolerance import RestartPolicy
from repro.models.transformer import ModelConfig
from repro.optim.adamw import OptConfig
from repro.training.trainer import train_loop

TINY = ModelConfig("tiny", n_layers=2, d_model=64, n_heads=4, n_kv=2,
                   d_ff=128, vocab=128)
OPT = OptConfig(lr_peak=1e-3, warmup_steps=5, total_steps=40)
DATA = DataConfig(vocab=128, seq_len=64, global_batch=8)


def test_loss_decreases():
    ocfg = OptConfig(lr_peak=3e-3, warmup_steps=20, total_steps=200)
    _, _, hist = train_loop(TINY, ocfg,
                            DataConfig(vocab=128, seq_len=64, global_batch=16),
                            120, verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.5


def test_restart_bit_identical(tmp_path):
    td = str(tmp_path)
    p1, _, _ = train_loop(TINY, OPT, DATA, 12, ckpt_dir=td,
                          policy=RestartPolicy(ckpt_every=5), verbose=False)
    # second run resumes from the final checkpoint: params unchanged
    p2, _, _ = train_loop(TINY, OPT, DATA, 12, ckpt_dir=td, verbose=False)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))


def test_crash_resume_equals_uninterrupted(tmp_path):
    """Simulated crash at step 10: resume must reproduce the 12-step run."""
    td = str(tmp_path)
    p_full, _, _ = train_loop(TINY, OPT, DATA, 12, verbose=False)
    train_loop(TINY, OPT, DATA, 10, ckpt_dir=td,
               policy=RestartPolicy(ckpt_every=5), verbose=False)
    # drop the step-12... keep only step 10, resume to 12
    p_res, _, _ = train_loop(TINY, OPT, DATA, 12, ckpt_dir=td, verbose=False)
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(a, b)


def test_corrupted_checkpoint_fallback(tmp_path):
    from repro.models.transformer import init_params
    from repro.optim.adamw import init_state
    td = str(tmp_path)
    train_loop(TINY, OPT, DATA, 12, ckpt_dir=td,
               policy=RestartPolicy(ckpt_every=5), verbose=False)
    latest = sorted(glob.glob(os.path.join(td, "step_*")))[-1]
    os.remove(glob.glob(os.path.join(latest, "leaf_00000.npy"))[0])
    params = init_params(jax.random.PRNGKey(0), TINY)
    example = {"params": params, "opt": init_state(params, OPT)}
    step, tree = store.restore_latest(td, example)
    # restore_latest must skip the corrupted dir and return an older step
    assert step is not None and step < 12
    assert tree is not None


def test_checkpoint_atomicity(tmp_path):
    """A .tmp dir (simulated crash mid-write) is ignored by restore."""
    td = str(tmp_path)
    tree = {"a": np.arange(4), "b": np.ones((2, 2))}
    store.save(td, 1, tree)
    os.makedirs(os.path.join(td, "step_00000002.tmp"))
    step, restored = store.restore_latest(td, tree)
    assert step == 1
    np.testing.assert_array_equal(restored["a"], tree["a"])


def test_data_pipeline_deterministic_and_seekable():
    cfg = DataConfig(vocab=100, seq_len=32, global_batch=8, seed=7)
    b1 = global_batch_at(5, cfg)
    b2 = global_batch_at(5, cfg)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = global_batch_at(6, cfg)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # elastic: per-host slices tile the global batch regardless of host count
    for nh in (1, 2, 4):
        parts = [host_batch_at(5, cfg, h, nh)["tokens"] for h in range(nh)]
        np.testing.assert_array_equal(np.concatenate(parts), b1["tokens"])


def test_step_watchdog():
    from repro.distributed.fault_tolerance import StepWatchdog
    import time
    with pytest.raises(TimeoutError):
        with StepWatchdog(0.1):
            time.sleep(0.5)
    with StepWatchdog(5.0):
        pass  # disarms cleanly


def test_compressed_psum_multidevice_subprocess():
    """Run the posit-compressed all-reduce on 8 emulated devices and compare
    against the exact f32 psum (error bounded by one posit16 rounding)."""
    import subprocess
    import sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.types import P16_2
from repro.distributed.collectives import compressed_psum

mesh = jax.make_mesh((8,), ("data",))
x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)

def f(xs):
    return compressed_psum(xs, "data", P16_2)

got = jax.shard_map(f, mesh=mesh, in_specs=P("data", None),
                    out_specs=P("data", None), check_vma=False)(x)
want = x.sum(axis=0, keepdims=True).repeat(8, 0)
rel = np.abs(np.asarray(got) - np.asarray(want)) / (np.abs(np.asarray(want)) + 1e-9)
assert rel.max() < 2e-3, rel.max()   # p16: ~2^-13 relative rounding + margin
print("OK", rel.max())
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_compile_cache_follows_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache goes
    to the fixed .jax_cache/ at the root of the checkout."""
    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.CACHE_ENV, "/some/cache")
        assert cc.enable_compile_cache() == "/some/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv(cc.CACHE_ENV)
        got = cc.enable_compile_cache()
        assert got == os.path.join(cc.REPO_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(cc.REPO_ROOT, "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_peaks_keyed_by_device_kind():
    from repro.launch.analysis import TARGET_KIND, peaks
    assert peaks(TARGET_KIND)["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no peak rates"):
        peaks("cpu")
