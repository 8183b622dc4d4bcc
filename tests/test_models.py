"""Per-arch smoke tests (reduced same-family configs) + model invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core.types import P8_2, P16_2
from repro.models.transformer import (ModelConfig, forward, init_caches,
                                      init_params)
from repro.quant.policy import PositPolicy


def _inputs(cfg, B=2, S=16):
    if cfg.input_mode == "embeddings":
        return dict(inputs_embeds=jnp.ones((B, S, cfg.d_model), jnp.float32))
    if cfg.input_mode == "tokens+image":
        return dict(tokens=jnp.zeros((B, S), jnp.int32),
                    inputs_embeds=jnp.ones((B, 4, cfg.d_model), jnp.float32))
    return dict(tokens=jnp.zeros((B, S), jnp.int32))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_smoke_forward(arch):
    cfg = configs.get_smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    logits, aux, _ = jax.jit(lambda p, kw: forward(p, cfg, **kw),
                             static_argnames=())(params, _inputs(cfg))
    B = 2
    S_out = 16 + (4 if cfg.input_mode == "tokens+image" else 0)
    assert logits.shape == (B, S_out, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_smoke_train_step(arch):
    from repro.optim.adamw import OptConfig, init_state
    from repro.training.train_step import train_step
    cfg = configs.get_smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_state(params, OptConfig())
    B, S = 2, 16
    if cfg.encoder_only:
        batch = {"embeds": jnp.ones((B, S, cfg.d_model), jnp.float32),
                 "labels": jnp.zeros((B, S), jnp.int32)}
    else:
        batch = {"tokens": jnp.ones((B, S + 1), jnp.int32)}
        if cfg.input_mode == "tokens+image":
            batch["image_embeds"] = jnp.ones((B, 4, cfg.d_model), jnp.float32)
    params2, opt2, metrics = jax.jit(
        lambda p, o, b: train_step(p, o, b, cfg, OptConfig()))(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    moved = any(not np.array_equal(a, b) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


def test_serving_matches_full_forward():
    cfg = ModelConfig("eq", n_layers=3, d_model=48, n_heads=4, n_kv=2,
                      d_ff=96, vocab=64)
    params = init_params(jax.random.PRNGKey(2), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 64)
    full, _, _ = forward(params, cfg, tokens=toks)
    caches = init_caches(cfg, 2, 32)
    lg, _, caches = forward(params, cfg, tokens=toks[:, :16], caches=caches)
    errs = [float(jnp.abs(lg[:, -1] - full[:, 15]).max())]
    for i in range(16, 24):
        lg, _, caches = forward(params, cfg, tokens=toks[:, i:i + 1],
                                caches=caches)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, i]).max()))
    assert max(errs) < 1e-4


def test_hybrid_serving_matches_full_forward():
    """recurrentgemma-style hybrid: rglru + local attention caches."""
    cfg = ModelConfig("rg-eq", n_layers=5, d_model=32, n_heads=2, n_kv=1,
                      d_ff=64, vocab=64, head_dim=16, act="geglu",
                      block_pattern=("rglru", "rglru", "attn_local"),
                      window=8)
    params = init_params(jax.random.PRNGKey(4), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 20), 0, 64)
    full, _, _ = forward(params, cfg, tokens=toks)
    caches = init_caches(cfg, 2, 24)
    lg, _, caches = forward(params, cfg, tokens=toks[:, :12], caches=caches)
    errs = [float(jnp.abs(lg[:, -1] - full[:, 11]).max())]
    for i in range(12, 20):
        lg, _, caches = forward(params, cfg, tokens=toks[:, i:i + 1],
                                caches=caches)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, i]).max()))
    assert max(errs) < 1e-4


def test_rwkv_serving_matches_full_forward():
    cfg = ModelConfig("rwkv-eq", n_layers=2, d_model=32, n_heads=2, n_kv=2,
                      d_ff=64, vocab=64, block_pattern=("rwkv6",),
                      rwkv_head_dim=16)
    params = init_params(jax.random.PRNGKey(6), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 16), 0, 64)
    full, _, _ = forward(params, cfg, tokens=toks)
    caches = init_caches(cfg, 1, 16)
    lg, _, caches = forward(params, cfg, tokens=toks[:, :8], caches=caches)
    errs = [float(jnp.abs(lg[:, -1] - full[:, 7]).max())]
    for i in range(8, 16):
        lg, _, caches = forward(params, cfg, tokens=toks[:, i:i + 1],
                                caches=caches)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, i]).max()))
    assert max(errs) < 1e-3


def test_posit_policy_close_to_f32():
    """posit16 weight QAT forward stays close to the f32 forward (the
    paper's 'p16 ~ binary32' claim at the LM scale of a smoke config)."""
    base = ModelConfig("pol", n_layers=2, d_model=64, n_heads=4, n_kv=2,
                       d_ff=128, vocab=128)
    params = init_params(jax.random.PRNGKey(8), base)
    toks = jnp.ones((2, 16), jnp.int32)
    ref, _, _ = forward(params, base, tokens=toks)
    import dataclasses
    for cfg_fmt, tol in ((P16_2, 0.02), (P8_2, 0.6)):
        qcfg = dataclasses.replace(base, policy=PositPolicy(weights=cfg_fmt))
        got, _, _ = forward(params, qcfg, tokens=toks)
        rel = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))
        assert rel < tol, (str(cfg_fmt), rel)


def test_ste_gradient_passthrough():
    from repro.quant.policy import posit_cast_ste
    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)), jnp.float32)
    g = jax.grad(lambda x: (posit_cast_ste(x, P16_2) ** 2).sum())(w)
    # STE: d/dw (q(w)^2) = 2*q(w) (gradient flows through cast unchanged)
    np.testing.assert_allclose(g, 2 * posit_cast_ste(w, P16_2), rtol=1e-6)


def _qk_norm_params(cfg, seed):
    """init_params with the q/k norm scales drawn away from 1, so that a
    missing scale shows."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    attn = params["scanned"][0]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):
        s = attn[name]["scale"]
        attn[name]["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(seed + 10 + i), s.shape)
    return params


def test_qk_norm_matches_plain_formula():
    """attention_block with qk_norm: q and k RMS-normalized over their whole
    projection widths (not per head), scaled, then rotated, as written out
    here in plain jnp."""
    from repro.models import blocks as B
    d, H, KV, hd, S = 32, 4, 2, 8, 6
    p = B.init_attention(jax.random.PRNGKey(0), d, H, KV, hd, qk_norm=True)
    p["q_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), (H * hd,))
    p["k_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (KV * hd,))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, d))
    pos = jnp.arange(S)[None]
    got, _ = B.attention_block(x, p, n_heads=H, n_kv=KV, head_dim=hd,
                               positions=pos, policy=PositPolicy())

    def plain(per_head):
        q, k, v = (x[0] @ p[n]["w"] for n in ("wq", "wk", "wv"))

        def rms(t, scale, width):
            t = t.reshape(S, -1, width)
            t = t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-6)
            return t.reshape(S, -1) * scale
        w = hd if per_head else None
        q = rms(q, p["q_norm"]["scale"], w or H * hd).reshape(S, H, hd)
        k = rms(k, p["k_norm"]["scale"], w or KV * hd).reshape(S, KV, hd)
        q = B.rope(q.transpose(1, 0, 2), pos)
        k = jnp.repeat(B.rope(k.transpose(1, 0, 2), pos), H // KV, 0)
        v = jnp.repeat(v.reshape(S, KV, hd).transpose(1, 0, 2), H // KV, 0)
        s = jnp.einsum("hqd,hkd->hqk", q, k) * hd ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(s, -1), v)
        return (o.reshape(S, H * hd) @ p["wo"]["w"])[None]

    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(False)),
                               rtol=1e-5, atol=1e-5)
    # a per-head norm is another model: the check can tell them apart
    assert float(jnp.abs(got - plain(True)).max()) > 1e-3


def test_qk_norm_serving_matches_full_forward():
    """With qk_norm, prefill then decode through the dense cache gives the
    full forward's logits."""
    cfg = ModelConfig("eq-qk", n_layers=2, d_model=48, n_heads=4, n_kv=2,
                      d_ff=96, vocab=64, qk_norm=True)
    params = _qk_norm_params(cfg, 20)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0, 64)
    full, _, _ = forward(params, cfg, tokens=toks)
    caches = init_caches(cfg, 2, 24)
    lg, _, caches = forward(params, cfg, tokens=toks[:, :12], caches=caches)
    errs = [float(jnp.abs(lg[:, -1] - full[:, 11]).max())]
    for i in range(12, 20):
        lg, _, caches = forward(params, cfg, tokens=toks[:, i:i + 1],
                                caches=caches)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, i]).max()))
    assert max(errs) < 1e-4
