"""Pallas TPU kernels: bulk posit <-> float codec (PFCVT, §VI).

HBM-bandwidth-bound kernels used wherever tensors cross the posit/float
boundary in bulk: weight dematerialisation, KV-cache (de)quantization and
the posit-compressed gradient collective.  Reading int8 and writing f32
moves 5 bytes/element instead of 8 for an f32->f32 copy — the paper's
storage-density benefit (C4) on the memory roofline term.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.convert import f32_to_posit
from repro.core.decode import decode_to_f32
from repro.core.types import PositConfig

_WIDTH = 8 * 128


def _reshape_tiles(x: jnp.ndarray, block_rows: int):
    flat = x.reshape(-1)
    rows = max(1, -(-flat.shape[0] // _WIDTH))
    rows = -(-rows // block_rows) * block_rows
    flat = jnp.pad(flat, (0, rows * _WIDTH - flat.shape[0]))
    return flat.reshape(rows, _WIDTH)


def _decode_kernel(p_ref, o_ref, *, cfg):
    o_ref[...] = decode_to_f32(p_ref[...], cfg)


def _encode_kernel(v_ref, o_ref, *, cfg):
    o_ref[...] = f32_to_posit(v_ref[...], cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "block_rows", "interpret"))
def decode_block(p: jnp.ndarray, cfg: PositConfig, *, block_rows: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Bulk posit -> f32 (exact)."""
    shape, size = p.shape, p.size
    t = _reshape_tiles(jnp.asarray(p), block_rows)
    grid = (t.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, _WIDTH), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, _WIDTH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(t.shape, jnp.float32),
        name="decode_block",
        interpret=interpret,
    )(t)
    return out.reshape(-1)[:size].reshape(shape)


@functools.partial(jax.jit, static_argnames=("cfg", "block_rows", "interpret"))
def encode_block(v: jnp.ndarray, cfg: PositConfig, *, block_rows: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Bulk f32 -> posit (RNE)."""
    shape, size = v.shape, v.size
    t = _reshape_tiles(jnp.asarray(v).astype(jnp.float32), block_rows)
    grid = (t.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_encode_kernel, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, _WIDTH), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, _WIDTH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(t.shape, jnp.dtype(f"int{cfg.storage_bits}")),
        name="encode_block",
        interpret=interpret,
    )(t)
    return out.reshape(-1)[:size].reshape(shape)
