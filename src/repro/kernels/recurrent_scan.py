"""Pallas TPU kernel: fused recurrent-scan step for RWKV6 / rGLRU serving.

Recurrent layers cache O(1) state per sequence instead of O(context) KV —
the extreme case of the paper's C4/C6 memory story — and the serving engine
stores that state as posit8/posit16 in the state pool.  This kernel runs the
per-token recurrence with the posit state decoded in VMEM, accumulated in
f32, and re-encoded in-kernel after every token (same idiom as
`paged_flash_decode`: HBM only ever sees the narrow ints).

The per-token round-trip is the serving-path quantization contract: because
every value that crosses a token boundary is used at its round-tripped
value, the scan is invariant to where prefill chunks split the prompt, and
the paged engine's chunked prefill + single-token decode reproduces dense
`generate()` bit-for-bit.

Grid layout puts the time axis last as an "arbitrary" dimension, in tiles
of up to 8 tokens unrolled in the kernel body, and carries the state in
VMEM scratch across it (the online-softmax accumulator pattern); batch (and
head, for WKV) axes are "parallel".  `num_new` is scalar-prefetched and
masks per-token updates at `t >= num_new[b]`, so inactive pool slots, and
the padded tail of the last time tile, carry their state through unchanged
(posit encode(decode(bits)) is the identity on canonical bits).

The jnp `lax.scan` twins (`*_ref`) implement the identical per-token math
and serve as the counted CPU/interpret oracle under `kernels.ops`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.convert import f32_to_posit
from repro.core.decode import decode_to_f32
from repro.core.types import PositConfig
from repro.kernels.tpu_params import compiler_params


def _rt(x, cfg: PositConfig | None):
    """Posit round-trip (quantize state to its storage format); identity
    when no posit policy is in force."""
    if cfg is None:
        return x
    return decode_to_f32(f32_to_posit(x, cfg), cfg)


def _load_state(ref_val, cfg, posit_state):
    if posit_state:
        return decode_to_f32(ref_val, cfg)
    return ref_val.astype(jnp.float32)


def _store_state(val, cfg, posit_state):
    if posit_state:
        return f32_to_posit(val, cfg)
    return val


# --------------------------------------------------------------------------
# WKV (RWKV6 time-mix core):
#   y_t = r_t . S_{t-1}  +  (sum_d r_t u k_t) v_t
#   S_t = rt( diag(exp(logw_t)) S_{t-1} + k_t^T v_t )
# --------------------------------------------------------------------------
def _time_tile(T: int) -> tuple[int, int]:
    """(rows per time tile, padded T).  Mosaic tiles the last two block dims
    by (8, 128) unless a block spans the whole axis, so time moves in tiles
    of 8 rows, or in one tile when T <= 8 (decode: T == 1).  Padded steps
    sit at t >= T >= num_new and are masked like any ragged tail."""
    if T <= 8:
        return T, T
    return 8, -(-T // 8) * 8


def _pad_time(x, axis: int, Tp: int):
    pad = Tp - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _wkv_kernel(nn_ref, r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sf_ref, s_scr, *, cfg_state, posit_state, tT, nT):
    b = pl.program_id(0)
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        s_scr[...] = _load_state(s0_ref[0, 0], cfg_state, posit_state)

    u = u_ref[0].astype(jnp.float32)                  # [1, dh]

    # outer products via contract-the-unit-axis dot_general (no transposes:
    # Mosaic dislikes 1D relayouts); E[d, :] = exp(w[d]) scales row d of S
    def outer(col, row):
        return jax.lax.dot_general(col, row, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    S = s_scr[...]                                    # [dh, dh] f32
    for i in range(tT):                               # static unroll
        r = r_ref[0, 0, i:i + 1, :].astype(jnp.float32)   # [1, dh]
        k = k_ref[0, 0, i:i + 1, :].astype(jnp.float32)
        v = v_ref[0, 0, i:i + 1, :].astype(jnp.float32)
        w = w_ref[0, 0, i:i + 1, :].astype(jnp.float32)

        y = jax.lax.dot_general(r, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        su = jnp.sum(r * u * k, axis=-1, keepdims=True)   # [1, 1] bonus
        y = y + su * v

        S_new = outer(jnp.exp(w), jnp.ones_like(v)) * S + outer(k, v)
        S_new = _rt(S_new, cfg_state)

        live = tb * tT + i < nn_ref[b]
        S = jnp.where(live, S_new, S)
        y_ref[0, 0, i:i + 1, :] = jnp.where(live, y, 0.0)
    s_scr[...] = S

    @pl.when(tb == nT - 1)
    def _done():
        sf_ref[0, 0] = _store_state(S, cfg_state, posit_state)


@functools.partial(jax.jit, static_argnames=("cfg_state", "posit_state",
                                             "interpret"))
def wkv_scan_pallas(r, k, v, logw, u, s0, num_new, *,
                    cfg_state: PositConfig | None, posit_state: bool,
                    interpret: bool = False):
    """r/k/v/logw [B, H, T, dh], u [H, dh], s0 [B, H, dh, dh] (posit storage
    ints when posit_state), num_new [B] int32 -> (y [B, H, T, dh] f32,
    s_fin same representation as s0)."""
    B, H, T, dh = r.shape
    tT, Tp = _time_tile(T)
    r, k, v, logw = (_pad_time(x, 2, Tp) for x in (r, k, v, logw))
    nT = Tp // tT
    seq = pl.BlockSpec((1, 1, tT, dh), lambda b, h, t, nn: (b, h, t, 0))
    state = pl.BlockSpec((1, 1, dh, dh), lambda b, h, t, nn: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nT),
        in_specs=[seq, seq, seq, seq,
                  # u as [H, 1, dh]: a (1, dh) block then spans the array's
                  # last two dims, which the (8, 128) tiling rule allows
                  pl.BlockSpec((1, 1, dh), lambda b, h, t, nn: (h, 0, 0)),
                  state],
        out_specs=[seq, state],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
    )
    y, sf = pl.pallas_call(
        functools.partial(_wkv_kernel, cfg_state=cfg_state,
                          posit_state=posit_state, tT=tT, nT=nT),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, H, Tp, dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, dh, dh), s0.dtype)),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        name="wkv_scan_pallas",
        interpret=interpret,
    )(num_new, r, k, v, logw, u[:, None, :], s0)
    return y[:, :, :T], sf


def wkv_scan_ref(r, k, v, logw, u, s0, num_new, *,
                 cfg_state: PositConfig | None, posit_state: bool):
    """jnp oracle: identical per-token math as `_wkv_kernel`."""
    S0 = (decode_to_f32(s0, cfg_state) if posit_state
          else s0.astype(jnp.float32))
    uf = u.astype(jnp.float32)
    rT = jnp.moveaxis(r.astype(jnp.float32), 2, 0)    # [T, B, H, dh]
    kT = jnp.moveaxis(k.astype(jnp.float32), 2, 0)
    vT = jnp.moveaxis(v.astype(jnp.float32), 2, 0)
    wT = jnp.moveaxis(logw.astype(jnp.float32), 2, 0)
    tt = jnp.arange(r.shape[2], dtype=jnp.int32)

    def body(S, inp):
        r_t, k_t, v_t, w_t, t = inp
        y = jnp.einsum("bhd,bhdv->bhv", r_t, S)
        su = jnp.einsum("bhd,hd,bhd->bh", r_t, uf, k_t)
        y = y + su[..., None] * v_t
        S_new = jnp.exp(w_t)[..., None] * S + k_t[..., None] * v_t[:, :, None, :]
        S_new = _rt(S_new, cfg_state)
        live = t < num_new                            # [B]
        S = jnp.where(live[:, None, None, None], S_new, S)
        y = jnp.where(live[:, None, None], y, 0.0)
        return S, y

    S_fin, ys = jax.lax.scan(body, S0, (rT, kT, vT, wT, tt))
    y = jnp.moveaxis(ys, 0, 2)
    return y, _store_state(S_fin, cfg_state, posit_state)


# --------------------------------------------------------------------------
# rGLRU (Griffin/RecurrentGemma core):  h_t = rt(a_t h_{t-1} + b_t), y = h_t
# (a/b are the batched gate projections, computed outside the scan)
# --------------------------------------------------------------------------
def _rglru_kernel(nn_ref, a_ref, b_ref, h0_ref, y_ref, hf_ref, h_scr, *,
                  cfg_state, posit_state, tT, nT):
    bb = pl.program_id(0)
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        h_scr[...] = _load_state(h0_ref[0], cfg_state, posit_state)

    h = h_scr[...]                                    # [1, d] f32
    for i in range(tT):                               # static unroll
        a = a_ref[0, i:i + 1, :].astype(jnp.float32)  # [1, d]
        bt = b_ref[0, i:i + 1, :].astype(jnp.float32)
        h_new = _rt(a * h + bt, cfg_state)

        live = tb * tT + i < nn_ref[bb]
        h = jnp.where(live, h_new, h)
        y_ref[0, i:i + 1, :] = jnp.where(live, h_new, 0.0)
    h_scr[...] = h

    @pl.when(tb == nT - 1)
    def _done():
        hf_ref[0] = _store_state(h, cfg_state, posit_state)


@functools.partial(jax.jit, static_argnames=("cfg_state", "posit_state",
                                             "interpret"))
def rglru_scan_pallas(a, b, h0, num_new, *,
                      cfg_state: PositConfig | None, posit_state: bool,
                      interpret: bool = False):
    """a/b [B, T, d], h0 [B, d] (posit storage ints when posit_state),
    num_new [B] int32 -> (h_seq [B, T, d] f32, h_fin same rep as h0)."""
    B, T, d = a.shape
    tT, Tp = _time_tile(T)
    a, b = _pad_time(a, 1, Tp), _pad_time(b, 1, Tp)
    nT = Tp // tT
    seq = pl.BlockSpec((1, tT, d), lambda bb, t, nn: (bb, t, 0))
    # the state as [B, 1, d]: its (1, d) block spans the last two dims
    state = pl.BlockSpec((1, 1, d), lambda bb, t, nn: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nT),
        in_specs=[seq, seq, state],
        out_specs=[seq, state],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    h, hf = pl.pallas_call(
        functools.partial(_rglru_kernel, cfg_state=cfg_state,
                          posit_state=posit_state, tT=tT, nT=nT),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Tp, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, d), h0.dtype)),
        compiler_params=compiler_params("parallel", "arbitrary"),
        name="rglru_scan_pallas",
        interpret=interpret,
    )(num_new, a, b, h0[:, None, :])
    return h[:, :T], hf[:, 0]


def rglru_scan_ref(a, b, h0, num_new, *,
                   cfg_state: PositConfig | None, posit_state: bool):
    """jnp oracle: identical per-token math as `_rglru_kernel`."""
    H0 = (decode_to_f32(h0, cfg_state) if posit_state
          else h0.astype(jnp.float32))
    aT = jnp.moveaxis(a.astype(jnp.float32), 1, 0)    # [T, B, d]
    bT = jnp.moveaxis(b.astype(jnp.float32), 1, 0)
    tt = jnp.arange(a.shape[1], dtype=jnp.int32)

    def body(h, inp):
        a_t, b_t, t = inp
        h_new = _rt(a_t * h + b_t, cfg_state)
        live = (t < num_new)[:, None]                 # [B, 1]
        h = jnp.where(live, h_new, h)
        return h, jnp.where(live, h_new, 0.0)

    h_fin, ys = jax.lax.scan(body, H0, (aT, bT, tt))
    return jnp.moveaxis(ys, 0, 1), _store_state(h_fin, cfg_state, posit_state)
