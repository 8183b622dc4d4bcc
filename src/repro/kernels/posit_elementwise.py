"""Pallas TPU kernels: bit-exact elementwise posit ops on the VPU.

The SIMD configuration of the paper (§VIII-A) realised natively: int8/int16
posit payloads fill TPU vector lanes at 4x/2x the density of f32, and each
lane runs the integer FPPU datapath (decode -> int32 mantissa op -> RNE
encode) from repro.core.ops — the same code, so kernels are bit-exact
against the golden model by construction; the pallas_call adds the HBM->VMEM
tile pipeline (the paper's 4-stage pipelining analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ops as pops
from repro.core.types import PositConfig

# (name -> (n_inputs, core fn))
_OPS = {
    "add": (2, pops.padd),
    "sub": (2, pops.psub),
    "mul": (2, pops.pmul),
    "fma": (3, pops.pfma),
}

_LANES = 128
_SUBLANES = 8


def _ew_kernel(*refs, op_fn, cfg):
    ins = [r[...] for r in refs[:-1]]
    refs[-1][...] = op_fn(*ins, cfg)


def _tile_1d(x: jnp.ndarray, block_rows: int):
    """Flatten to (rows, 8*128) tiles; returns (tiled, orig_len, rows)."""
    flat = x.reshape(-1)
    width = _SUBLANES * _LANES
    rows = max(1, -(-flat.shape[0] // width))
    rows_pad = -(-rows // block_rows) * block_rows
    pad = rows_pad * width - flat.shape[0]
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows_pad, width), x.size


@functools.partial(jax.jit, static_argnames=("op", "cfg", "block_rows", "interpret"))
def elementwise(op: str, *inputs, cfg: PositConfig, block_rows: int = 64,
                interpret: bool = False) -> jnp.ndarray:
    """Apply a posit op elementwise via a Pallas VPU kernel.

    inputs: posit storage-int arrays of identical shape.  div uses the
    dedicated kernel in this module (extra mode arg).
    """
    n_in, fn = _OPS[op]
    assert len(inputs) == n_in, (op, len(inputs))
    shape = inputs[0].shape
    dt = inputs[0].dtype
    tiled = [_tile_1d(jnp.asarray(x), block_rows)[0] for x in inputs]
    size = inputs[0].size
    rows, width = tiled[0].shape
    grid = (rows // block_rows,)

    out = pl.pallas_call(
        functools.partial(_ew_kernel, op_fn=fn, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, width), lambda i: (i, 0))
                  for _ in range(n_in)],
        out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width), dt),
        name="elementwise",
        interpret=interpret,
    )(*tiled)
    return out.reshape(-1)[:size].reshape(shape)


def _div_kernel(a_ref, b_ref, o_ref, *, cfg, mode, nr_rounds):
    o_ref[...] = pops.pdiv(a_ref[...], b_ref[...], cfg, mode=mode,
                           nr_rounds=nr_rounds)


def _div_kernel_lut(a_ref, b_ref, lut_ref, o_ref, *, cfg, mode, nr_rounds):
    # pacogen mode: the reciprocal LUT rides along as a kernel input
    # (Pallas forbids captured constants); patch it into the lookup fn
    from repro.core import recip as _recip
    lut = lut_ref[0]

    def lookup(mb_frac, cfg2):
        from repro.core.decode import work_frac_bits
        Wd = work_frac_bits(cfg2)
        if Wd >= _recip.PACOGEN_LUT_IN:
            idx = mb_frac >> (Wd - _recip.PACOGEN_LUT_IN)
        else:
            idx = mb_frac << (_recip.PACOGEN_LUT_IN - Wd)
        return (jnp.take(lut, idx.reshape(-1)).reshape(idx.shape)
                .astype(jnp.int32))

    orig = _recip.pacogen_lut_i32
    _recip.pacogen_lut_i32 = lookup
    try:
        o_ref[...] = pops.pdiv(a_ref[...], b_ref[...], cfg, mode=mode,
                               nr_rounds=nr_rounds)
    finally:
        _recip.pacogen_lut_i32 = orig


@functools.partial(jax.jit, static_argnames=("cfg", "mode", "nr_rounds",
                                             "block_rows", "interpret"))
def divide(a, b, *, cfg: PositConfig, mode: str = "poly_corrected",
           nr_rounds: int = 1, block_rows: int = 64,
           interpret: bool = False) -> jnp.ndarray:
    """Elementwise posit division kernel (paper §V-A datapath).

    mode: "poly" (paper-faithful approximate), "pacogen" (Table II baseline),
    "poly_corrected"/"exact" (correctly rounded).
    """
    shape, dt = a.shape, a.dtype
    ta, size = _tile_1d(jnp.asarray(a), block_rows)
    tb, _ = _tile_1d(jnp.asarray(b), block_rows)
    rows, width = ta.shape
    grid = (rows // block_rows,)
    if mode == "pacogen":
        from repro.core.recip import _PACOGEN_LUT
        lut = jnp.asarray(_PACOGEN_LUT)[None, :]
        out = pl.pallas_call(
            functools.partial(_div_kernel_lut, cfg=cfg, mode=mode,
                              nr_rounds=nr_rounds),
            grid=grid,
            in_specs=[pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
                      pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
                      pl.BlockSpec((1, lut.shape[1]), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, width), dt),
            name="divide",
            interpret=interpret,
        )(ta, tb, lut)
        return out.reshape(-1)[:size].reshape(shape)
    out = pl.pallas_call(
        functools.partial(_div_kernel, cfg=cfg, mode=mode, nr_rounds=nr_rounds),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width), dt),
        name="divide",
        interpret=interpret,
    )(ta, tb)
    return out.reshape(-1)[:size].reshape(shape)
