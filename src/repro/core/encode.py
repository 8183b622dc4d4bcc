"""JAX posit encode — FPPU stage (iii): normalization + round-to-nearest-even.

Implements the paper's §IV-D: split te into regime k / exponent e, assemble
[sign | regime | exp | fraction], round with the (G, R, S) bits of Fig. 3,
and saturate (clip k per eq. (9)) to maxpos/minpos — a nonzero value never
rounds to zero or NaR (posit standard).

All arithmetic is int32 and branch-free.  The monotonicity of posit bit
patterns lets RNE act directly on the assembled pattern: increment iff
R & (S | G) — carries propagate through fraction/exponent/regime correctly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.types import PositConfig


def encode_fir(s, te, M, W: int, sticky, cfg: PositConfig) -> jnp.ndarray:
    """RNE-encode (-1)^s * 2^te * (M / 2^W) to posit bits (int32, N-bit).

    M must be normalized: M in [2^W, 2^(W+1)).  W is a static python int
    (<= 29).  `sticky` is 0/1 per element: OR of all discarded value bits
    below M's LSB.  Callers handle ZERO/NAR lanes.
    """
    n, es = cfg.n, cfg.es
    s = jnp.asarray(s, dtype=jnp.int32)
    te = jnp.asarray(te, dtype=jnp.int32)
    M = jnp.asarray(M, dtype=jnp.int32)
    sticky = jnp.asarray(sticky, dtype=jnp.int32)

    # values beyond the representable exponent range saturate (paper eq. (9)
    # clip): > maxpos -> maxpos, < minpos -> minpos (never 0/NaR).  Record the
    # masks before clipping — the clipped assembly would otherwise round a
    # sub-minpos value up across the boundary.
    sat_hi = te > cfg.te_max
    sat_lo = te < cfg.te_min
    te = jnp.clip(te, cfg.te_min, cfg.te_max)
    k = te >> es
    e = te - (k << es)

    # regime field: k>=0 -> (k+1) ones + stop 0 ; k<0 -> (-k) zeros + stop 1
    k_pos = k >= 0
    rlen = jnp.where(k_pos, k + 2, 1 - k)            # <= n
    regime = jnp.where(k_pos, ((jnp.int32(1) << (jnp.minimum(k, n) + 1)) - 1) << 1, 1)

    frac = M - (jnp.int32(1) << W)
    nre = rlen + es
    body_bits = n - 1
    combined_re = (regime << es) | e                 # <= n + es + 1 bits

    # --- case A: some fraction bits survive (nre < n-1) ---
    ffield = jnp.maximum(body_bits - nre, 0)
    shiftA = jnp.clip(W - ffield, 1, 31)             # >= 4 in practice (W >= n-3+?')
    keptA = frac >> shiftA
    rA = (frac >> (shiftA - 1)) & 1
    sA = ((frac & ((jnp.int32(1) << (shiftA - 1)) - 1)) != 0).astype(jnp.int32) | sticky
    bodyA = (combined_re << ffield) | keptA

    # --- case B: regime+exponent fill the body (nre >= n-1) ---
    shiftB = jnp.clip(nre - body_bits, 0, 31)
    bodyB = combined_re >> shiftB
    shiftB1 = jnp.maximum(shiftB - 1, 0)
    rB = jnp.where(shiftB > 0, (combined_re >> shiftB1) & 1, (frac >> (W - 1)) & 1)
    low_re = (combined_re & ((jnp.int32(1) << shiftB1) - 1)) != 0
    low_fr_all = frac != 0
    low_fr_tail = (frac & ((jnp.int32(1) << (W - 1)) - 1)) != 0
    # select between int32 flags, never between bools: Mosaic lowers a
    # boolean select through an i8 -> i1 truncation it cannot compile
    sB = jnp.where(shiftB > 0, (low_re | low_fr_all).astype(jnp.int32),
                   low_fr_tail.astype(jnp.int32)) | sticky

    caseA = nre < body_bits
    body = jnp.where(caseA, bodyA, bodyB)
    r = jnp.where(caseA, rA, rB)
    st = jnp.where(caseA, sA, sB)

    g = body & 1
    body = body + (r & (st | g))                     # RNE on the monotone pattern

    body = jnp.minimum(body, cfg.maxpos_bits)        # round-up past maxpos
    body = jnp.maximum(body, cfg.minpos_bits)        # nonzero never rounds to 0
    body = jnp.where(sat_hi, cfg.maxpos_bits, body)
    body = jnp.where(sat_lo, cfg.minpos_bits, body)

    return jnp.where(s == 1, (-body) & cfg.mask, body)


def to_storage(p: jnp.ndarray, cfg: PositConfig) -> jnp.ndarray:
    """int32 N-bit patterns -> the format's storage dtype (sign-extended)."""
    bits = cfg.storage_bits
    shift = 32 - bits if cfg.n == bits else 32 - cfg.n
    # left-align then arithmetic shift right to sign-extend the N-bit pattern
    x = (p << (32 - cfg.n)) >> (32 - cfg.n)
    return x.astype(jnp.dtype(f"int{bits}"))
