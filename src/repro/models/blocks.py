"""Transformer building blocks — pure functional JAX (params are pytrees).

Conventions:
  * params: nested dicts of jnp arrays; init_* functions build them from a
    PRNG key; apply functions are pure.
  * activations f32 (dry-run/CPU) or bf16 via ModelConfig.dtype; matmuls
    accumulate f32.
  * posit weight policy: when cfg.policy.weights is set, weight matrices go
    through posit_cast_ste (training, QAT semantics) so the forward sees
    exactly the deployed posit values.  Serving uses pre-quantized int
    weights via kernels.pw_matmul.
  * attention is blockwise (flash-style online softmax) in pure jnp —
    O(S) memory, scan-based — so 32k prefill lowers without an S x S buffer;
    the Pallas kernel path replaces it on real TPUs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.array import PositArray
from repro.core.types import PositConfig
from repro.quant.policy import PositPolicy, posit_cast_ste

Params = dict[str, Any]


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------
def _dense_init(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(x, p: Params, eps: float = 1e-6):
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(var + eps)
    return (h * p["scale"]).astype(x.dtype)


def init_layernorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(x, p: Params, eps: float = 1e-6):
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * p["scale"] + p["bias"]).astype(x.dtype)


# --------------------------------------------------------------------------
# linear with posit weight policy
# --------------------------------------------------------------------------
def init_linear(key, d_in: int, d_out: int, bias: bool = False) -> Params:
    p = {"w": _dense_init(key, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear(x, p: Params, policy: PositPolicy | None = None):
    w = p["w"]
    if isinstance(w, PositArray):
        # serving path: pre-quantized posit weights carry their own format;
        # decode is fused in the kernel
        from repro.kernels import ops as kops
        y = kops.pw_matmul(x, w).astype(x.dtype)
    elif w.dtype in (jnp.int8, jnp.int16):
        # deprecated shim: raw posit bits, format threaded via the policy
        from repro.kernels import ops as kops
        assert policy is not None and policy.weights is not None, (
            "int posit weights require policy.weights")
        y = kops.pw_matmul(x, w, policy.weights).astype(x.dtype)
    else:
        if policy is not None and policy.weights is not None:
            w = posit_cast_ste(w, policy.weights)
        from repro.kernels import ops as kops
        if kops.use_pallas() and not kops.force_reference():
            # training / float-weight kernel path: same posit_gemm kernel,
            # differentiable end to end (gemm's custom_vjp runs the dX/dW
            # kernels), so QAT training engages the MXU pipeline too
            lead = x.shape[:-1]
            y = kops.gemm(x.reshape(-1, x.shape[-1]), w)
            y = y.reshape(*lead, w.shape[-1]).astype(x.dtype)
        else:
            y = jnp.einsum("...i,io->...o", x, w,
                           preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# --------------------------------------------------------------------------
# stateful single-step serving helpers (recurrent blocks; serving/backends)
# --------------------------------------------------------------------------
def rt_values(x, pcfg):
    """Posit round-trip decode(encode(x)) — identity when pcfg is None.

    The serving-side state quantization rule: every value that crosses a
    step boundary (carried state, token shifts, conv tails) is *used* at
    its round-tripped value, so the computation is independent of where
    prefill chunks split the sequence and of whether the state was stored
    as raw floats (dense cache tuples) or posit bits (the state pool) —
    both decode to the same values.  Round-tripping is idempotent, so
    applying it at use as well as at store costs nothing numerically."""
    if pcfg is None:
        return x
    from repro.core.convert import f32_to_posit
    from repro.core.decode import decode_to_f32
    return decode_to_f32(f32_to_posit(x.astype(jnp.float32), pcfg), pcfg)


def select_last(x, num_new):
    """x [B, S, ...] -> the last *valid* position per row: x[b, num_new[b]-1]
    (clipped into range; rows with num_new == 0 return position 0, which the
    caller masks).  num_new None means every row is fully valid: x[:, -1]."""
    if num_new is None:
        return x[:, -1]
    idx = jnp.clip(num_new - 1, 0, x.shape[1] - 1)
    idx = idx.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------
def rope(x, positions, theta: float = 10000.0):
    """x [..., S, D] with D even; positions [..., S] (int)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs        # [..., S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# blockwise (flash-style) attention in pure jnp
# --------------------------------------------------------------------------
_NEG = -1e30


def blockwise_attention(q, k, v, *, n_kv: int, causal: bool, q_offset=0,
                        window: int | None = None, q_chunk: int = 512,
                        kv_chunk: int = 512, softcap: float | None = None,
                        kv_len=None, cfg_kv=None):
    """GQA-aware flash-style attention, O(chunk^2) memory.

    q [B,H,Sq,D]; k/v [B,KV,Skv,D] with H = KV*G — the group dim is kept
    explicit (no jnp.repeat materialization).  k/v may be `PositArray` (the
    format travels with the pages; `cfg_kv` stays unset) or raw posit
    storage ints with the deprecated explicit `cfg_kv`: each KV chunk is
    decoded to f32 right before its matmul, mirroring the Pallas kernel's
    fused dequant — HBM traffic stays at posit width and no full-cache
    float copy ever exists.

    On the Pallas path, Sq > 1 dispatches to the fused prefill kernel
    (kernels.ops.flash_prefill): the training forward and the dense
    engine's chunked prefill run the same kernel serving prefill uses, with
    this function's jnp scan as the bit-parity reference — and as the
    backward (jax.custom_vjp recomputes the reference VJP, flash-attention
    style, so nothing score-shaped is ever saved).

    q_offset: absolute position of q[0] (decode: cache length; may be traced;
        scalar or per-sequence [B] for the paged engine's ragged batches).
    kv_len: number of valid KV positions (dynamic; default Skv; scalar or
        per-sequence [B]).
    window: sliding-window size (local attention, recurrentgemma).
    """
    from repro.core.array import unwrap_kv
    k, v, cfg_kv = unwrap_kv(k, v, cfg_kv, q=q)
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if kv_len is None:
        kv_len = Skv
    # normalize to a [B]-or-[1] vector: per-sequence lengths/offsets (paged
    # continuous batching) and scalars share one code path; broadcasting a
    # [1]-vector is bit-identical to the old scalar math
    kv_len = jnp.asarray(kv_len)
    kv_len = kv_len[None] if kv_len.ndim == 0 else kv_len
    q_off = jnp.asarray(q_offset)
    q_off = q_off[None] if q_off.ndim == 0 else q_off

    from repro.kernels import ops as kops
    if Sq > 1 and kops.use_pallas() and not kops.force_reference():
        static = (cfg_kv, n_kv, causal, window, softcap)
        qo = jnp.broadcast_to(q_off.astype(jnp.int32), (B,))
        kl = jnp.broadcast_to(kv_len.astype(jnp.int32), (B,))
        return _fused_prefill(static, q, k, v, kl, qo).astype(q.dtype)
    return _blockwise_jnp(q, k, v, n_kv=n_kv, causal=causal, q_off=q_off,
                          window=window, q_chunk=q_chunk, kv_chunk=kv_chunk,
                          softcap=softcap, kv_len=kv_len, cfg_kv=cfg_kv)


def _blockwise_jnp(q, k, v, *, n_kv: int, causal: bool, q_off, window,
                   q_chunk: int, kv_chunk: int, softcap, kv_len, cfg_kv):
    """The pure-jnp scan (k/v raw, q_off/kv_len already [B]-or-[1]): the
    reference/oracle body and the non-Pallas execution path."""
    B, H, Sq, D = q.shape
    KV = n_kv
    G = H // KV
    Skv = k.shape[2]
    scale = D ** -0.5

    if Sq == 1:
        # decode fast path (flash-decoding layout): no scan — S-contraction
        # einsums let GSPMD keep the KV cache fully sharded on its sequence
        # dim; the only cross-device traffic is the softmax stats and the
        # (B,H,1,D) output psum (§Perf iteration B2)
        def _dec1(t):
            if cfg_kv is not None:
                from repro.core.decode import decode_to_f32
                return decode_to_f32(t, cfg_kv)
            return t.astype(jnp.float32)

        from repro.distributed.sharding import shard_activation
        kf, vf = _dec1(k), _dec1(v)
        if G > 1:
            kf = jnp.repeat(kf, G, axis=1)
            vf = jnp.repeat(vf, G, axis=1)
        # pin the flash-decoding layout: tiny q replicated over the TP axis,
        # KV stays sequence-sharded -> only stats/output psums cross chips
        kf = shard_activation(kf, "kv_seq")
        vf = shard_activation(vf, "kv_seq")
        q = shard_activation(q, "batch_only")
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        kpos = jnp.arange(Skv)
        valid = kpos[None, :] < kv_len[:, None]
        if window is not None:
            valid = valid & (kpos[None, :] > kv_len[:, None] - 1 - window)
        s = jnp.where(valid[:, None, None, :], s, _NEG)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vf,
                         preferred_element_type=jnp.float32)
        out = out / p.sum(axis=-1, keepdims=True)
        return out.astype(q.dtype)

    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    pq = (-Sq) % qc
    pk = (-Skv) % kc
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    nq, nk = (Sq + pq) // qc, (Skv + pk) // kc

    kb = kp.reshape(B, KV, nk, kc, D).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(B, KV, nk, kc, D).transpose(2, 0, 1, 3, 4)
    qb = qp.reshape(B, H, nq, qc, D).transpose(2, 0, 1, 3, 4)

    def _dec(t):
        if cfg_kv is not None:
            from repro.core.decode import decode_to_f32
            return decode_to_f32(t, cfg_kv)
        return t.astype(jnp.float32)

    def q_block(qi, q_tile):                     # q_tile [B,H,qc,D]
        qpos = q_off[:, None] + qi * qc + jnp.arange(qc)[None, :]  # [B|1, qc]

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, k_tile, v_tile = inputs          # [B,KV,kc,D] (posit/float)
            # per-chunk decode + GQA head expansion: transient, chunk-sized —
            # the q-side head sharding propagates through the einsum while
            # the kv source stays narrow in HBM
            k_tile = _dec(k_tile)
            v_tile = _dec(v_tile)
            if G > 1:
                k_tile = jnp.repeat(k_tile, G, axis=1)
                v_tile = jnp.repeat(v_tile, G, axis=1)
            kpos = ki * kc + jnp.arange(kc)
            s = jnp.einsum("bhqd,bhkd->bhqk",
                           q_tile.astype(jnp.float32), k_tile,
                           preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            valid = kpos[None, None, :] < kv_len[:, None, None]  # [B|1,1,kc]
            if causal:
                valid = valid & (qpos[:, :, None] >= kpos[None, None, :])
            if window is not None:
                valid = valid & (qpos[:, :, None] - kpos[None, None, :]
                                 < window)
            s = jnp.where(valid[:, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_tile,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, qc), _NEG, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, D), jnp.float32)
        # remat each kv step: score/prob blocks are recomputed in the backward
        # (flash-attention memory behaviour), never saved per block pair
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (m0, l0, a0), (jnp.arange(nk), kb, vb))
        return acc / jnp.where(l == 0, 1.0, l)[..., None]

    # checkpoint per q-block: lax.map saves only block inputs; one block's
    # kv-scan carry chain is live at a time in the backward
    outs = jax.lax.map(
        jax.checkpoint(lambda args: q_block(*args),
                       policy=jax.checkpoint_policies.nothing_saveable),
        (jnp.arange(nq), qb))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq + pq, D)[:, :, :Sq]
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_prefill(static, q, k, v, kv_len, q_off):
    """Fused prefill forward with a kernel (or counted-oracle) VJP.

    static = (cfg_kv, n_kv, causal, window, softcap) — hashable, so one
    custom_vjp covers every arch.  The forward runs the Pallas kernel
    (posit KV decodes in VMEM, no dense copy); when differentiated it also
    saves (o, lse) so the backward can rebuild the scores tile by tile —
    `kernels.ops.flash_prefill_bwd` dispatches the flash dQ/dK/dV kernels,
    falling back to differentiating `_blockwise_jnp` (counted in
    `ops.BWD_FALLBACKS`) off the kernel path.  Integer operands (posit KV
    bits, lengths/offsets) carry no tangents and get None cotangents.
    """
    cfg_kv, n_kv, causal, window, softcap = static
    from repro.kernels import ops as kops
    return kops.flash_prefill(q, k, v, kv_len, q_off, cfg_kv=cfg_kv,
                              causal=causal, window=window, softcap=softcap)


def _fused_prefill_fwd(static, q, k, v, kv_len, q_off):
    cfg_kv, n_kv, causal, window, softcap = static
    from repro.kernels import ops as kops
    out, lse = kops.flash_prefill(q, k, v, kv_len, q_off, cfg_kv=cfg_kv,
                                  causal=causal, window=window,
                                  softcap=softcap, return_lse=True)
    return out, (q, k, v, out, lse, kv_len, q_off)


def _fused_prefill_bwd(static, res, g):
    cfg_kv, n_kv, causal, window, softcap = static
    q, k, v, o, lse, kv_len, q_off = res
    from repro.kernels import ops as kops
    dq, dk, dv = kops.flash_prefill_bwd(
        q, k, v, o, lse, g, kv_len, q_off, n_kv=n_kv, cfg_kv=cfg_kv,
        causal=causal, window=window, softcap=softcap)
    if jnp.issubdtype(k.dtype, jnp.floating):
        return dq, dk, dv, None, None
    # posit KV (serving): bits are integers, only q carries a tangent
    return dq, None, None, None, None


_fused_prefill.defvjp(_fused_prefill_fwd, _fused_prefill_bwd)


# --------------------------------------------------------------------------
# GQA attention block
# --------------------------------------------------------------------------
def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False, qk_norm: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], d_model, n_heads * head_dim, qkv_bias),
        "wk": init_linear(ks[1], d_model, n_kv * head_dim, qkv_bias),
        "wv": init_linear(ks[2], d_model, n_kv * head_dim, qkv_bias),
        "wo": init_linear(ks[3], n_heads * head_dim, d_model, False),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(n_heads * head_dim)
        p["k_norm"] = init_rmsnorm(n_kv * head_dim)
    return p


def _qk_norm(q, k, p: Params, ctx, eps: float = 1e-6):
    """RMSNorm of q [B, S, H*hd] and k [B, S, KV*hd] over their whole
    projection widths (OLMoE's q/k norm).  Under a tensor_parallel context
    this member holds a column slice of each projection (and of each
    scale): one psum of the per-member sums of squares of both gives every
    member the full widths' means."""
    if ctx is None:
        return rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    ss = jax.lax.psum(jnp.stack([jnp.sum(qf * qf, axis=-1),
                                 jnp.sum(kf * kf, axis=-1)]), ctx.axis)

    def scaled(h, s, scale):
        var = s[..., None] / (h.shape[-1] * ctx.size)
        return h * jax.lax.rsqrt(var + eps) * scale
    return (scaled(qf, ss[0], p["q_norm"]["scale"]).astype(q.dtype),
            scaled(kf, ss[1], p["k_norm"]["scale"]).astype(k.dtype))


def attention_block(x, p: Params, *, n_heads: int, n_kv: int, head_dim: int,
                    positions, policy: PositPolicy, causal: bool = True,
                    window: int | None = None, rope_theta: float = 10000.0,
                    kv_cache=None, softcap: float | None = None):
    """Returns (out, new_kv_cache).  kv_cache: dict(k, v, length) or None.

    With `q_norm`/`k_norm` in `p` (ModelConfig.qk_norm), q and k are
    RMS-normalized over their whole projection widths before the head
    reshape and RoPE, on every path (training, dense and paged caches).

    k/v cache tensors are [B, n_kv, S_max, head_dim]; PositArray pages when
    the cache was initialized with a posit format (decoded for compute here,
    fused in the Pallas kernel on TPU) — the format rides with the pages, so
    nothing here re-states it.
    """
    from repro.distributed.collectives import (block_grad_sync, block_psum,
                                               tp_ctx)
    ctx = tp_ctx()
    if ctx is not None:
        # Megatron TP (sharded serving or training step): wq/wk/wv are
        # column-parallel, so this member computes its n_heads/ntp heads
        # (and n_kv/ntp kv heads, whose pages live on the same member); wo
        # is row-parallel and owes the block's one psum below.  The
        # f-operator makes the block's d(input) whole again when training
        # differentiates through the weight shards (identity forward).
        n_heads //= ctx.size
        n_kv //= ctx.size
        x = block_grad_sync(x)
    B, S, _ = x.shape
    if "q_norm" in p:
        # q/k RMSNorm over the full projection widths, before RoPE
        q, k = _qk_norm(linear(x, p["wq"], policy),
                        linear(x, p["wk"], policy), p, ctx)
        q = q.reshape(B, S, n_heads, head_dim)
        k = k.reshape(B, S, n_kv, head_dim)
    else:
        q = linear(x, p["wq"], policy).reshape(B, S, n_heads, head_dim)
        k = linear(x, p["wk"], policy).reshape(B, S, n_kv, head_dim)
    v = linear(x, p["wv"], policy).reshape(B, S, n_kv, head_dim)

    q = rope(q.transpose(0, 2, 1, 3), positions[:, None, :], rope_theta)
    k = rope(k.transpose(0, 2, 1, 3), positions[:, None, :], rope_theta)
    v = v.transpose(0, 2, 1, 3)

    new_cache = None
    kv_len = None
    legacy_cfg = None
    if kv_cache is not None and "page_table" in kv_cache:
        # paged pool (continuous batching): scatter-append the new tokens
        # into this layer's pages, then attend through the paged path —
        # fused Pallas paged-gather decode on TPU, gather+blockwise on CPU
        from repro.serving.paged_kv import paged_append_kv, paged_attention
        q_offset = kv_cache["seq_lens"]             # per-sequence, traced
        new_cache = paged_append_kv(kv_cache, k, v)
        out = paged_attention(q, new_cache, n_kv=n_kv, causal=causal,
                              q_offset=q_offset, window=window,
                              softcap=softcap)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, n_heads * head_dim)
        return block_psum(linear(out, p["wo"], policy)), new_cache
    if kv_cache is not None:
        from repro.serving.kv_cache import append_kv
        q_offset = kv_cache["length"]               # traced scalar
        # legacy raw-int posit caches (pre-PositArray convention) still need
        # the policy-threaded format; PositArray pages carry their own
        if (not isinstance(kv_cache["k"], PositArray)
                and jnp.issubdtype(kv_cache["k"].dtype, jnp.integer)):
            legacy_cfg = policy.kv_cache
        new_cache = append_kv(kv_cache, k, v, legacy_cfg)
        # pass the buffers as-is (PositArray pages stay posit): chunks
        # decode in-scan, with the format read off the pages themselves
        k, v = new_cache["k"], new_cache["v"]
        kv_len = new_cache["length"]
    else:
        q_offset = k.shape[2] - S

    out = blockwise_attention(q, k, v, n_kv=n_kv, causal=causal,
                              q_offset=q_offset, window=window,
                              softcap=softcap, kv_len=kv_len,
                              cfg_kv=legacy_cfg)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, n_heads * head_dim)
    return block_psum(linear(out, p["wo"], policy)), new_cache


# --------------------------------------------------------------------------
# MLP (dense) block
# --------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, act: str) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": init_linear(ks[0], d_model, d_ff),
         "w_down": init_linear(ks[1], d_ff, d_model)}
    if act in ("geglu", "swiglu"):
        p["w_gate"] = init_linear(ks[2], d_model, d_ff)
    return p


def mlp_block(x, p: Params, *, act: str, policy: PositPolicy):
    # f-operator (identity fwd / TP-psum bwd): w_up/w_gate shards each see
    # only their d_ff slice, so d(x) comes back partial per member
    from repro.distributed.collectives import block_grad_sync
    x = block_grad_sync(x)
    up = linear(x, p["w_up"], policy)
    if act == "geglu":
        h = jax.nn.gelu(linear(x, p["w_gate"], policy)) * up
    elif act == "swiglu":
        h = jax.nn.silu(linear(x, p["w_gate"], policy)) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    elif act == "relu":
        h = jax.nn.relu(up)
    else:
        raise ValueError(act)
    # under TP (sharded serving) w_up/w_gate are column-parallel over d_ff
    # and w_down row-parallel: the partial product owes the block's one psum
    from repro.distributed.collectives import block_psum
    return block_psum(linear(h, p["w_down"], policy))


# --------------------------------------------------------------------------
# embedding with posit storage option
# --------------------------------------------------------------------------
def init_embedding(key, vocab: int, d_model: int) -> Params:
    return {"table": jax.random.normal(key, (vocab, d_model),
                                       dtype=jnp.float32) * (d_model ** -0.5)}


def embed(tokens, p: Params, policy: PositPolicy):
    t = p["table"]
    from repro.distributed.collectives import tp_ctx
    ctx = tp_ctx()
    if ctx is not None and ctx.vocab_sharded:
        # Megatron vocab-parallel embedding: this member holds rows
        # [off, off + v_local); out-of-range tokens gather a masked zero row
        # and the psum assembles each embedding from exactly one nonzero
        # member — 0 + x is exact, so logits stay bit-identical to the
        # unsharded lookup.
        v_local = t.shape[0]
        local = tokens - jax.lax.axis_index(ctx.axis) * v_local
        ok = (local >= 0) & (local < v_local)
        idx = jnp.clip(local, 0, v_local - 1)
        if isinstance(t, PositArray):
            rows = t[idx].to_f32()
        elif t.dtype in (jnp.int8, jnp.int16):
            from repro.core.decode import decode_to_f32
            rows = decode_to_f32(jnp.take(t, idx, axis=0), policy.weights)
        else:
            if policy is not None and policy.weights is not None:
                t = posit_cast_ste(t, policy.weights)
            rows = jnp.take(t, idx, axis=0)
        rows = jnp.where(ok[..., None], rows, 0.0)
        return jax.lax.psum(rows, ctx.axis)
    if isinstance(t, PositArray):
        # Light-PPU use case [9]: posit storage of tables, decode after
        # gather — the table knows its own format
        return t[tokens].to_f32()
    if t.dtype in (jnp.int8, jnp.int16):
        # deprecated shim: raw posit bits + policy-threaded format
        from repro.core.decode import decode_to_f32
        rows = jnp.take(t, tokens, axis=0)
        return decode_to_f32(rows, policy.weights)
    if policy is not None and policy.weights is not None:
        t = posit_cast_ste(t, policy.weights)
    return jnp.take(t, tokens, axis=0)


def unembed(h, p: Params, policy: PositPolicy):
    """h [..., d] @ tied-table [V, d].T -> logits [..., V].

    Posit tables route through pw_gemm with transpose_b: the [V, d] table —
    the decode step's largest single tensor — streams at posit width and
    decodes tile-by-tile in VMEM, instead of materializing the full f32
    table every step.  Under vocab-parallel TP the local [V/ntp, d] shard
    takes the same path.  The jnp reference contracts the identical
    dot_general dims, so logits stay bit-identical across backends.
    """
    t = p["table"]
    if isinstance(t, PositArray) or jnp.issubdtype(t.dtype, jnp.integer):
        from repro.kernels import ops as kops
        cfg = None if isinstance(t, PositArray) else policy.weights
        return kops.pw_matmul(h, t, cfg, transpose_b=True)
    if policy is not None and policy.weights is not None:
        t = posit_cast_ste(t, policy.weights)
    from repro.kernels import ops as kops
    if kops.use_pallas() and not kops.force_reference():
        # float/QAT table on the kernel path: same transpose_b stream, and
        # gemm's custom_vjp gives the dH/dTable kernels for training
        lead = h.shape[:-1]
        out = kops.gemm(h.reshape(-1, h.shape[-1]), t, transpose_b=True)
        return out.reshape(*lead, t.shape[0])
    return jnp.einsum("...d,vd->...v", h, t,
                      preferred_element_type=jnp.float32)
