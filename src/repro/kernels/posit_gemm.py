"""Pallas TPU kernel: posit GEMM with in-kernel decode and quire-style
accumulation (the FPPU's PFMADD/quire datapath mapped onto the MXU).

TPU adaptation of the paper's compute pipeline (DESIGN.md §2):
  stage (i)  decode:      posit tiles (int8/int16) -> exact f32 in VMEM
  stage (ii) compute:     MXU matmul, f32 accumulator = the quire analogue
  stage (iii) normalize:  single RNE encode of the accumulator (optional)

The Pallas grid pipeline double-buffers HBM->VMEM tile fetches across grid
steps — the TPU realisation of the FPPU's 4-stage pipelining (§V).

Because operands travel as 8/16-bit integers, HBM traffic is 1/4 / 1/2 of
an f32 GEMM (the paper's SIMD-register-density argument, §VIII-A) — this is
what moves the memory roofline term in EXPERIMENTS.md §Perf.

Two kernels:
  * posit_gemm:  A[posit] @ B[posit] -> f32 or posit
  * pw_gemm:     A[f32/bf16] @ B[posit] -> f32   (posit-weight hot path)
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


def _pad_to(x: jnp.ndarray, m0: int, m1: int, value=0) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=value)
    return x


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, cfg_a, cfg_b, nk, out_posit,
                 cfg_out, transpose_a, transpose_b):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if cfg_a is not None:
        a = decode_to_f32(a, cfg_a)          # exact dequant, stage (i)
    else:
        a = a.astype(jnp.float32)
    b = b_ref[...]
    if cfg_b is not None:
        b = decode_to_f32(b, cfg_b)
    else:
        b = b.astype(jnp.float32)

    # transposed operands contract on their stored axis (a tile [bk, bm]:
    # dim 0; b tile [bn, bk]: dim 1) — the transposed layout never
    # materializes, in VMEM or HBM
    ca = 0 if transpose_a else 1
    cb = 1 if transpose_b else 0
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        acc = acc_ref[...]
        if out_posit:
            o_ref[...] = f32_to_posit(acc, cfg_out)   # stage (iii): one rounding
        else:
            o_ref[...] = acc


# i/j tiles own disjoint output blocks; only the k axis carries the
# accumulator and must stay ordered
_GEMM_SEMANTICS = ("parallel", "parallel", "arbitrary")

# decoding *both* 512x512 operand tiles in VMEM (posit x posit) keeps ~19 MiB
# of int32/f32 temporaries live, past Mosaic's 16 MiB default scope; v5e has
# 128 MiB of VMEM per core
_GEMM_VMEM_LIMIT = 32 * 2**20


@functools.partial(
    jax.jit,
    static_argnames=("cfg_a", "cfg_b", "cfg_out", "out_posit", "bm", "bn",
                     "bk", "transpose_a", "transpose_b", "interpret"),
)
def posit_gemm(a: jnp.ndarray, b: jnp.ndarray, *,
               cfg_a: PositConfig | None, cfg_b: PositConfig | None,
               cfg_out: PositConfig | None = None, out_posit: bool = False,
               bm: int = 512, bn: int = 512, bk: int = 512,
               transpose_a: bool = False, transpose_b: bool = False,
               interpret: bool = False) -> jnp.ndarray:
    """[m,k] @ [k,n] (or [m,k] @ [n,k].T when transpose_b, or
    [k,m].T @ [k,n] when transpose_a) with posit operands decoded in-kernel.

    cfg_a/cfg_b None means that operand is already float.  Output is f32
    (quire-accumulated) or posit bits when out_posit (single final rounding).
    transpose_a is the dW leg of the training backward (dW = A^T @ G): the
    stored activation tile contracts on its leading dim, so no XLA
    transpose of the [m, k] operand ever materializes.
    Block shapes: MXU-aligned multiples of 128.  Roofline defaults: HBM
    traffic is m*k*(n/bn) + k*n*(m/bm) operand bytes, so square 512-blocks
    halve the re-read term vs the old 256x256 while the f32 working set
    (decoded a + b + acc = 3 MB, double-buffered narrow-int inputs on top)
    still fits VMEM with headroom; the k axis stays at 512 so one tile pair
    amortizes its fetch over >= 512 MACs/element — past the MXU ridge even
    at posit8 (1 byte/elem) width.
    """
    if transpose_a:
        k, m = a.shape
    else:
        m, k = a.shape
    if transpose_b:
        n, k2 = b.shape
    else:
        k2, n = b.shape
    assert k == k2, (a.shape, b.shape, transpose_a, transpose_b)
    bm_ = min(bm, max(8, m)); bn_ = min(bn, max(128, n)); bk_ = min(bk, k)
    a = _pad_to(a, bk_, bm_) if transpose_a else _pad_to(a, bm_, bk_)
    b = _pad_to(b, bn_, bk_) if transpose_b else _pad_to(b, bk_, bn_)
    mp = a.shape[1] if transpose_a else a.shape[0]
    kp = a.shape[0] if transpose_a else a.shape[1]
    np_ = b.shape[0] if transpose_b else b.shape[1]
    grid = (mp // bm_, np_ // bn_, kp // bk_)

    if out_posit:
        out_dtype = jnp.dtype(f"int{cfg_out.storage_bits}")
    else:
        out_dtype = jnp.float32

    if transpose_a:
        a_spec = pl.BlockSpec((bk_, bm_), lambda i, j, kk: (kk, i))
    else:
        a_spec = pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk))
    if transpose_b:
        b_spec = pl.BlockSpec((bn_, bk_), lambda i, j, kk: (j, kk))
    else:
        b_spec = pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j))
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, cfg_a=cfg_a, cfg_b=cfg_b, nk=grid[2],
                          out_posit=out_posit, cfg_out=cfg_out,
                          transpose_a=transpose_a, transpose_b=transpose_b),
        grid=grid,
        in_specs=[
            a_spec,
            b_spec,
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=compiler_params(*_GEMM_SEMANTICS,
                                        vmem_limit_bytes=_GEMM_VMEM_LIMIT),
        name="posit_gemm",
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


def pw_gemm(x: jnp.ndarray, w_bits: jnp.ndarray, cfg: PositConfig, *,
            bm: int = 512, bn: int = 512, bk: int = 512,
            transpose_b: bool = False,
            interpret: bool = False) -> jnp.ndarray:
    """Activations[f32/bf16, m x k] @ posit-weights[k x n] -> f32.

    The LM forward/serving hot path: weights stream from HBM at posit width
    and are decoded in VMEM right before the MXU.  transpose_b: the weight
    is stored [n, k] (the tied unembedding table) and contracted on its
    last dim in-kernel.
    """
    return posit_gemm(x, w_bits, cfg_a=None, cfg_b=cfg, out_posit=False,
                      bm=bm, bn=bn, bk=bk, transpose_b=transpose_b,
                      interpret=interpret)
