"""Kernel dispatch: Pallas on TPU, pure-jnp reference path elsewhere.

The model zoo calls these wrappers; the CPU dry-run/AOT compile lowers the
jnp path (Pallas-for-TPU cannot lower on the CPU backend), real TPU runs
take the fused kernels, and tests exercise both via interpret=True.

Operands may be `PositArray` (format travels with the array; the `cfg_*`
keywords stay unset) or raw storage-int arrays with an explicit config (the
original, now-deprecated calling convention — kept as a shim).  When a
posit-typed result is produced from PositArray inputs it comes back as a
PositArray; raw-bit inputs keep getting raw bits out.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp

from repro.core.array import (PositArray, PositConfigMismatchError,
                              result_cfg, unwrap_kv)
from repro.core.types import PositConfig
from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_gemm as _ggemm
from repro.kernels import posit_codec as _codec
from repro.kernels import posit_elementwise as _ew
from repro.kernels import posit_gemm as _gemm
from repro.kernels import ref as _ref


def use_pallas() -> bool:
    env = os.environ.get("REPRO_USE_PALLAS")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Run the Pallas kernels in interpret mode (REPRO_PALLAS_INTERPRET=1).

    With REPRO_USE_PALLAS=1 this executes the *kernel* code paths on the
    CPU backend — the tier-1 suite uses it to drive whole engines through
    the fused attention/gemm kernels (and to assert the gather_kv fallback
    is never taken) without TPU hardware."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    return env is not None and env not in ("0", "false", "False")


# in-process equivalent of REPRO_FORCE_GATHER=1 (tests/benches that cannot
# re-exec); both are consulted by every fused-attention dispatch site, so
# forcing the baseline forces the *whole* gather_kv + jnp blockwise path —
# a gather leg can never half-dispatch back into a fused kernel
FORCE_REFERENCE = False


def force_reference() -> bool:
    """Force the jnp reference paths even where `use_pallas()` would fuse
    (REPRO_FORCE_GATHER=1 or ops.FORCE_REFERENCE) — the baseline leg of the
    prefill/TTFT benchmarks, which measure the fused kernels against the
    gather_kv + blockwise dense-materialization path they replaced."""
    if FORCE_REFERENCE:
        return True
    env = os.environ.get("REPRO_FORCE_GATHER")
    return env is not None and env not in ("0", "false", "False")


def dispatch_key() -> tuple:
    """The trace-time dispatch state: (use_pallas, force_reference,
    pallas_interpret).  Jitted step builders that are cached across calls
    key on it, so flipping the Pallas path off (a reference leg) retraces
    instead of reusing a step traced with the kernels."""
    return use_pallas(), force_reference(), pallas_interpret()


# Pin the jnp-reference *backwards* while the forwards keep their kernels
# (REPRO_FORCE_BWD_REFERENCE=1 or ops.FORCE_BWD_REFERENCE): the baseline
# leg of benchmarks/train_step.py, and the oracle leg of the grad-parity
# tests — a forced backward is still counted in BWD_FALLBACKS.
FORCE_BWD_REFERENCE = False


def force_bwd_reference() -> bool:
    if FORCE_BWD_REFERENCE:
        return True
    env = os.environ.get("REPRO_FORCE_BWD_REFERENCE")
    return env is not None and env not in ("0", "false", "False")


# Every backward that does NOT take a Pallas kernel is counted here, keyed
# "<op>:forced" (a kernel was available but FORCE_* pinned the reference)
# or "<op>:jnp-reference" (no Pallas backend) — the training analogue of
# GATHER_FALLBACKS / DENSE_MOE_FALLBACKS / RECURRENT_FALLBACKS, asserted
# zero by the shard_map train-step test and logged by training.trainer.
BWD_FALLBACKS = collections.Counter()


def _count_bwd_fallback(op: str) -> None:
    BWD_FALLBACKS[f"{op}:" + ("forced" if use_pallas()
                              else "jnp-reference")] += 1


def _split(x, cfg: PositConfig | None):
    """(operand, explicit-cfg) -> (raw bits/array, cfg, was_posit_array)."""
    if isinstance(x, PositArray):
        if cfg is not None and cfg != x.cfg:
            raise PositConfigMismatchError(
                f"explicit cfg {cfg} contradicts operand format {x.cfg}")
        return x.bits, x.cfg, True
    return x, cfg, False


def _resolve_elementwise(op: str, inputs, cfg: PositConfig | None):
    """Shared PositArray resolution for the elementwise-shaped ops:
    returns (raw input tuple, cfg, any_posit).  Raw companions of
    PositArray operands must be payload ints — float values consumed as
    bit patterns are silent corruption."""
    any_posit = any(isinstance(x, PositArray) for x in inputs)
    if any_posit:
        cfg = result_cfg(*inputs, cfg=cfg)
        for x in inputs:
            if isinstance(x, PositArray):
                continue
            dt = getattr(x, "dtype", None)
            if (isinstance(x, (bool, int, float, complex))
                    or (dt is not None and jnp.issubdtype(dt, jnp.floating))):
                # python scalars are values, float arrays are values: both
                # would be consumed as bit patterns here.  Only raw *int
                # arrays* pass through (the documented payload-bits shim).
                raise TypeError(
                    f"{op}: cannot mix a PositArray with a python scalar or "
                    f"float array — encode values with pnp.asarray(x, cfg) "
                    f"or wrap payload bits with pnp.frombits")
    if cfg is None:
        raise TypeError(f"{op} needs PositArray inputs or an explicit cfg")
    raw = tuple(x.bits if isinstance(x, PositArray) else x for x in inputs)
    # broadcast to a common shape here, not in the kernels: the Pallas
    # elementwise path tiles each input independently and would silently
    # mis-align scalar/broadcast operands (the jnp ref path broadcasts
    # anyway, so this is free there)
    shape = jnp.broadcast_shapes(*(jnp.shape(x) for x in raw))
    raw = tuple(jnp.broadcast_to(x, shape) for x in raw)
    return raw, cfg, any_posit


def gemm(a, b, *, cfg_a: PositConfig | None = None,
         cfg_b: PositConfig | None = None,
         cfg_out: PositConfig | None = None, out_posit: bool = False,
         transpose_b: bool = False):
    a, cfg_a, a_posit = _split(a, cfg_a)
    b, cfg_b, b_posit = _split(b, cfg_b)
    # cfg-less *int* operands would be matmul'd as integer values: posit
    # payload bits always need their format (floats are activations and
    # legitimately skip the decode)
    for raw, raw_cfg in ((a, cfg_a), (b, cfg_b)):
        dt = getattr(raw, "dtype", None)
        if (raw_cfg is None and dt is not None
                and jnp.issubdtype(dt, jnp.integer)):
            raise TypeError(
                "gemm: int payload bits need their format — wrap them with "
                "pnp.frombits(bits, cfg) or pass cfg_a/cfg_b")
    if out_posit and cfg_out is None:
        if (cfg_a is not None and cfg_b is not None and cfg_a != cfg_b):
            raise PositConfigMismatchError(
                f"mixed-format gemm ({cfg_a} @ {cfg_b}) with out_posit needs "
                f"an explicit cfg_out")
        cfg_out = cfg_a if cfg_a is not None else cfg_b
    if out_posit:
        # posit bits out: no tangent through the rounding — direct dispatch
        if use_pallas():
            out = _gemm.posit_gemm(a, b, cfg_a=cfg_a, cfg_b=cfg_b,
                                   cfg_out=cfg_out, out_posit=True,
                                   transpose_b=transpose_b,
                                   interpret=pallas_interpret())
        else:
            out = _ref.posit_gemm_ref(a, b, cfg_a=cfg_a, cfg_b=cfg_b,
                                      cfg_out=cfg_out, out_posit=True,
                                      transpose_b=transpose_b)
        if a_posit or b_posit:
            return PositArray(out, cfg_out)
        return out
    static = (cfg_a, cfg_b, transpose_b, use_pallas(), pallas_interpret())
    return _gemm_mm(static, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_mm(static, a, b):
    cfg_a, cfg_b, transpose_b, use_kernel, interpret = static
    if use_kernel:
        return _gemm.posit_gemm(a, b, cfg_a=cfg_a, cfg_b=cfg_b,
                                transpose_b=transpose_b, interpret=interpret)
    return _ref.posit_gemm_ref(a, b, cfg_a=cfg_a, cfg_b=cfg_b,
                               transpose_b=transpose_b)


def _gemm_mm_fwd(static, a, b):
    return _gemm_mm(static, a, b), (a, b)


def _gemm_mm_bwd(static, res, g):
    """dA = G @ B^T and dB = A^T @ G through the same posit_gemm kernel the
    forward used: posit operands stream at storage width and decode in VMEM
    (transpose_a/transpose_b index the stored tiles, so no transposed copy
    exists), with f32 quire-style accumulation.  Posit operands carry no
    tangent — training crosses the posit boundary through the STE.  Off the
    kernel path the jnp reference runs and the miss is counted."""
    cfg_a, cfg_b, transpose_b, use_kernel, interpret = static
    a, b = res
    g = g.astype(jnp.float32)
    if use_kernel and not force_bwd_reference():
        if cfg_a is not None:
            da = None
        else:
            # forward b layout: [k,n] (or [n,k] when transpose_b) — dA
            # contracts g with the *other* storage axis
            da = _gemm.posit_gemm(g, b, cfg_a=None, cfg_b=cfg_b,
                                  transpose_b=not transpose_b,
                                  interpret=interpret).astype(a.dtype)
        if cfg_b is not None:
            db = None
        elif transpose_b:
            db = _gemm.posit_gemm(g, a, cfg_a=None, cfg_b=cfg_a,
                                  transpose_a=True,
                                  interpret=interpret).astype(b.dtype)
        else:
            db = _gemm.posit_gemm(a, g, cfg_a=cfg_a, cfg_b=None,
                                  transpose_a=True,
                                  interpret=interpret).astype(b.dtype)
        return da, db
    _count_bwd_fallback("gemm")
    from repro.core.decode import decode_to_f32
    af = (decode_to_f32(a, cfg_a) if cfg_a is not None
          else a.astype(jnp.float32))
    bf = (decode_to_f32(b, cfg_b) if cfg_b is not None
          else b.astype(jnp.float32))
    da = None
    if cfg_a is None:
        da = (g @ bf if transpose_b else g @ bf.T).astype(a.dtype)
    db = None
    if cfg_b is None:
        db = (g.T @ af if transpose_b else af.T @ g).astype(b.dtype)
    return da, db


_gemm_mm.defvjp(_gemm_mm_fwd, _gemm_mm_bwd)


def pw_matmul(x, w, cfg: PositConfig | None = None, *,
              transpose_b: bool = False):
    """[..., k] @ posit-weight [k, n] -> f32 (the LM linear-layer hot path).

    `w` is a PositArray (preferred) or raw storage ints + explicit `cfg`
    (deprecated shim).  transpose_b: `w` is stored [n, k] and contracted on
    its last dim — the unembedding path, where the tied [vocab, d] table
    must stream at posit width without materializing a transposed (or
    decoded) copy.
    """
    w, cfg, _ = _split(w, cfg)
    if cfg is None:
        raise TypeError("pw_matmul needs a PositArray weight or explicit cfg")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = gemm(x2, w, cfg_a=None, cfg_b=cfg, transpose_b=transpose_b)
    return out.reshape(*lead, w.shape[0] if transpose_b else w.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_mm(static, x, w, group_offsets):
    cfg, use_kernel, interpret = static
    if use_kernel:
        return _ggemm.posit_grouped_gemm(x, w, group_offsets, cfg_b=cfg,
                                         interpret=interpret)
    return _ref.grouped_matmul_ref(x, w, group_offsets, cfg_b=cfg)


def _grouped_mm_fwd(static, x, w, group_offsets):
    return _grouped_mm(static, x, w, group_offsets), (x, w, group_offsets)


def _grouped_mm_bwd(static, res, g):
    """Backward dispatch: the grouped Pallas kernels when the forward fused,
    the jnp reference (counted in BWD_FALLBACKS) otherwise.

    Kernel leg: dx = g @ w[gid]^T runs `posit_grouped_gemm(transpose_b=
    True)` over the *same* [E, k, n] storage layout — posit experts stream
    at posit width and decode in VMEM in the backward too, replacing the
    full `decode_to_f32(w)` this path used to materialize; dw accumulates
    each group's x^T g in f32 VMEM scratch (`posit_grouped_gemm_dw`, the
    per-group quire).  Reference leg: per-row weight gather + one-hot
    three-operand einsum (XLA picks an O(S*E*max(k,n)) contraction, never
    the [S, k, n] outer-product tensor).  Integer operands (posit weight
    bits, the offsets) carry no tangents — training crosses the posit
    boundary through the STE."""
    cfg, use_kernel, interpret = static
    x, w, off = res
    gid, inb = _ref.grouped_row_ids(off, x.shape[0])
    g = jnp.where(inb[:, None], g.astype(jnp.float32), 0.0)
    if use_kernel and not force_bwd_reference():
        dx = _ggemm.posit_grouped_gemm(g, w, off, cfg_b=cfg,
                                       transpose_b=True,
                                       interpret=interpret).astype(x.dtype)
        if cfg is not None:
            return dx, None, None
        dw = _ggemm.posit_grouped_gemm_dw(
            x.astype(jnp.float32), g, off,
            interpret=interpret).astype(w.dtype)
        return dx, dw, None
    _count_bwd_fallback("grouped")
    if cfg is not None:
        from repro.core.decode import decode_to_f32
        wf = decode_to_f32(w, cfg)
    else:
        wf = w.astype(jnp.float32)
    dx = jnp.einsum("sn,skn->sk", g, wf[gid],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg is not None:
        return dx, None, None
    oh = jnp.where(inb[:, None], jax.nn.one_hot(gid, w.shape[0]), 0.0)
    dw = jnp.einsum("se,sk,sn->ekn", oh, x.astype(jnp.float32), g,
                    preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw, None


_grouped_mm.defvjp(_grouped_mm_fwd, _grouped_mm_bwd)


def grouped_matmul(x, w, group_offsets, *, cfg: PositConfig | None = None,
                   interpret: bool | None = None):
    """Expert-sorted rows x [S, k] @ per-group weights w [E, k, n] -> [S, n]
    f32 (the MoE grouped hot path; see models/moe.py).

    Rows [group_offsets[g], group_offsets[g+1]) contract against w[g]; rows
    at or past group_offsets[-1] come back as exact zeros.  `w` is a
    PositArray (preferred), raw storage ints + explicit `cfg`, or a float
    array (cfg None).  On the Pallas path the grouped kernel streams only
    the active groups' posit tiles and decodes them in VMEM; elsewhere the
    dense jnp reference runs.  Differentiable via jax.custom_vjp: on the
    kernel path both directions fuse (dx streams the storage-layout experts
    via transpose_b, dw accumulates per group in f32 scratch); elsewhere
    the jnp reference backward runs and is counted in BWD_FALLBACKS (posit
    weight bits carry no tangent — training crosses the posit boundary
    through the STE, exactly as pw_matmul does).
    """
    w, cfg, _ = _split(w, cfg)
    dt = getattr(w, "dtype", None)
    if (cfg is None and dt is not None and jnp.issubdtype(dt, jnp.integer)):
        raise TypeError(
            "grouped_matmul: int payload bits need their format — wrap them "
            "with pnp.frombits(bits, cfg) or pass cfg")
    use_kernel = use_pallas() and not force_reference()
    if interpret is None:
        interpret = pallas_interpret()
    static = (cfg, use_kernel, bool(interpret))
    return _grouped_mm(static, x, w,
                       jnp.asarray(group_offsets, jnp.int32))


def elementwise(op: str, *inputs, cfg: PositConfig | None = None):
    raw, cfg, any_posit = _resolve_elementwise(f"elementwise('{op}')",
                                               inputs, cfg)
    if use_pallas():
        out = _ew.elementwise(op, *raw, cfg=cfg, interpret=pallas_interpret())
    else:
        out = _ref.elementwise_ref(op, *raw, cfg=cfg)
    return PositArray(out, cfg) if any_posit else out


def divide(a, b, *, cfg: PositConfig | None = None,
           mode: str = "poly_corrected", nr_rounds: int = 1):
    (a, b), cfg, any_posit = _resolve_elementwise("divide", (a, b), cfg)
    if use_pallas():
        out = _ew.divide(a, b, cfg=cfg, mode=mode, nr_rounds=nr_rounds,
                         interpret=pallas_interpret())
    else:
        out = _ref.divide_ref(a, b, cfg=cfg, mode=mode, nr_rounds=nr_rounds)
    return PositArray(out, cfg) if any_posit else out


def decode(p, cfg: PositConfig | None = None):
    """Posit payload -> f32 values."""
    p, cfg, _ = _split(p, cfg)
    if cfg is None:
        raise TypeError("decode needs a PositArray or explicit cfg")
    if use_pallas():
        return _codec.decode_block(p, cfg, interpret=pallas_interpret())
    return _ref.decode_ref(p, cfg)


def encode(v, cfg: PositConfig):
    """f32 values -> posit payload bits (raw; wrap via pnp.asarray for a
    PositArray)."""
    if use_pallas():
        return _codec.encode_block(v, cfg, interpret=pallas_interpret())
    return _ref.encode_ref(v, cfg)


def attention(q, k, v, *, cfg_kv: PositConfig | None = None,
              causal: bool = True):
    """[BH, Sq, D] attention over (possibly posit) KV."""
    k, v, cfg_kv = unwrap_kv(k, v, cfg_kv, q=q)
    if use_pallas():
        return _fa.flash_attention(q, k, v, cfg_kv=cfg_kv, causal=causal,
                                   interpret=pallas_interpret())
    return _ref.flash_attention_ref(q, k, v, cfg_kv=cfg_kv, causal=causal)


def paged_prefill_attention(q, k_pages, v_pages, page_table, seq_lens,
                            q_offset, *, cfg_kv: PositConfig | None = None,
                            causal: bool = True, window: int | None = None,
                            softcap: float | None = None,
                            interpret: bool | None = None):
    """Fused paged prefill: q [B, H, Sq, D] x the paged KV pool.

    The TPU-only chunked-prefill hot path (serving.paged_kv.paged_attention
    routes here whenever `use_pallas()`); the pure-jnp oracle is
    gather_kv + models.blocks.blockwise_attention.  Pages may be PositArray
    (format travels with the pool) or raw ints + cfg_kv.
    """
    k_pages, v_pages, cfg_kv = unwrap_kv(k_pages, v_pages, cfg_kv, q=q)
    if interpret is None:
        interpret = pallas_interpret()
    return _fa.paged_flash_prefill(
        q, k_pages, v_pages, page_table, seq_lens, q_offset, cfg_kv=cfg_kv,
        causal=causal, window=window, softcap=softcap, interpret=interpret)


# Serving-path recurrent scans (RWKV6 WKV / rGLRU).  Every dispatch that
# does NOT take the fused Pallas kernel is counted here, keyed by why
# ("forced": REPRO_FORCE_GATHER overrode an available kernel;
# "jnp-reference": no Pallas backend) — the recurrent analogue of
# paged_kv.GATHER_FALLBACKS, asserted zero by the kernel-path serving tests.
RECURRENT_FALLBACKS = collections.Counter()


def wkv_scan(r, k, v, logw, u, s0, *, num_new=None,
             cfg_state: PositConfig | None = None):
    """RWKV6 WKV recurrence over a chunk (the serving scan core).

    r/k/v/logw [B, H, T, dh], u [H, dh].  s0 [B, H, dh, dh] is the carried
    state: a PositArray (the paged engine's posit state pool — decoded in
    VMEM, f32-accumulated, re-encoded in-kernel) or an f32 array (dense
    cache tuples / posit-off serving).  Under a posit state format
    (PositArray s0, or explicit `cfg_state` for f32 state under a posit KV
    policy) the state is round-tripped through the format after *every*
    token, so the scan is invariant to prefill chunking and the dense and
    pooled representations compute identical values.  num_new [B] masks
    per-slot ragged chunks (None = every row advances all T tokens).
    Returns (y [B, H, T, dh] f32, s_fin in s0's representation).
    """
    from repro.kernels import recurrent_scan as _rs
    s0_raw, cfg_state, posit_state = _split(s0, cfg_state)
    B, _, T, _ = r.shape
    nn = (jnp.full((B,), T, jnp.int32) if num_new is None
          else jnp.asarray(num_new, jnp.int32))
    if use_pallas() and not force_reference():
        y, sf = _rs.wkv_scan_pallas(r, k, v, logw, u, s0_raw, nn,
                                    cfg_state=cfg_state,
                                    posit_state=posit_state,
                                    interpret=pallas_interpret())
    else:
        RECURRENT_FALLBACKS["forced" if use_pallas()
                            else "jnp-reference"] += 1
        y, sf = _rs.wkv_scan_ref(r, k, v, logw, u, s0_raw, nn,
                                 cfg_state=cfg_state,
                                 posit_state=posit_state)
    return y, PositArray(sf, cfg_state) if posit_state else sf


def rglru_scan(a, b, h0, *, num_new=None,
               cfg_state: PositConfig | None = None):
    """rGLRU recurrence h_t = rt(a_t h + b_t) over a chunk (Griffin /
    RecurrentGemma serving core); a/b [B, T, d] are the batched gate
    projections.  h0 [B, d] follows the same PositArray-or-f32 state (and
    `cfg_state` round-trip) contract as `wkv_scan`.  Returns
    (h_seq [B, T, d] f32, h_fin in h0's representation)."""
    from repro.kernels import recurrent_scan as _rs
    h0_raw, cfg_state, posit_state = _split(h0, cfg_state)
    B, T, _ = a.shape
    nn = (jnp.full((B,), T, jnp.int32) if num_new is None
          else jnp.asarray(num_new, jnp.int32))
    if use_pallas() and not force_reference():
        h, hf = _rs.rglru_scan_pallas(a, b, h0_raw, nn,
                                      cfg_state=cfg_state,
                                      posit_state=posit_state,
                                      interpret=pallas_interpret())
    else:
        RECURRENT_FALLBACKS["forced" if use_pallas()
                            else "jnp-reference"] += 1
        h, hf = _rs.rglru_scan_ref(a, b, h0_raw, nn, cfg_state=cfg_state,
                                   posit_state=posit_state)
    return h, PositArray(hf, cfg_state) if posit_state else hf


def flash_prefill(q, k, v, kv_len, q_offset, *,
                  cfg_kv: PositConfig | None = None, causal: bool = True,
                  window: int | None = None, softcap: float | None = None,
                  return_lse: bool = False, interpret: bool | None = None):
    """Fused prefill over a contiguous KV cache (GQA layout).

    q [B, H, Sq, D] x k/v [B, n_kv, Skv, D]; kv_len/q_offset [B] int32.
    The TPU dispatch target of models.blocks.blockwise_attention (training
    forward and the dense engine's prefill), which remains the bit-parity
    reference; the dense cache streams tile-by-tile at storage width.
    return_lse: also return the row log-sum-exps — the residual the
    training backward (flash_prefill_bwd) consumes.
    """
    k, v, cfg_kv = unwrap_kv(k, v, cfg_kv, q=q)
    if interpret is None:
        interpret = pallas_interpret()
    return _fa.flash_prefill_contiguous(
        q, k, v, kv_len, q_offset, cfg_kv=cfg_kv, causal=causal,
        window=window, softcap=softcap, return_lse=return_lse,
        interpret=interpret)


def flash_prefill_bwd(q, k, v, o, lse, g, kv_len, q_offset, *, n_kv: int,
                      cfg_kv: PositConfig | None = None, causal: bool = True,
                      window: int | None = None, softcap: float | None = None,
                      interpret: bool | None = None):
    """(dQ, dK, dV) for the fused contiguous prefill.

    Kernel path: the flash backward kernels (dQ sweeps kv tiles, dK/dV
    sweep q tiles, scores rebuilt from the saved lse — no [Sq, Skv] matrix,
    posit KV decoded in VMEM).  Otherwise the jnp blockwise oracle is
    differentiated and the miss is counted in BWD_FALLBACKS.  Posit KV
    (cfg_kv set) returns dK = dV = None on both legs — storage ints carry
    no tangent.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if use_pallas() and not force_reference() and not force_bwd_reference():
        dq, dk, dv = _fa.flash_prefill_bwd_contiguous(
            q, k, v, o, lse, g, kv_len, q_offset, cfg_kv=cfg_kv,
            causal=causal, window=window, softcap=softcap,
            interpret=interpret)
        dq = dq.astype(q.dtype)
        if dk is not None:
            dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)
        return dq, dk, dv
    _count_bwd_fallback("flash")
    from repro.models.blocks import _blockwise_jnp

    def ref(qq, kk, vv):
        return _blockwise_jnp(qq, kk, vv, n_kv=n_kv, causal=causal,
                              q_off=q_offset, window=window, q_chunk=512,
                              kv_chunk=512, softcap=softcap, kv_len=kv_len,
                              cfg_kv=cfg_kv)

    if cfg_kv is not None:
        out, vjp = jax.vjp(lambda qq: ref(qq, k, v), q)
        (dq,) = vjp(g.astype(out.dtype))
        return dq, None, None
    out, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp(g.astype(out.dtype))
    return dq, dk, dv
