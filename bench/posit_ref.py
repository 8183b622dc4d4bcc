"""Posit rounding for the plain reference, written from the format's
definition (posit standard, 2022) and independent of the program's codec.

A posit<n, es> value 1.f x 2^E is stored as a sign bit, a regime for
k = floor(E / 2^es) (k+1 ones and a zero for k >= 0, -k zeros and a one
for k < 0), es exponent bits and what is left for the fraction.  Inside
one binade [2^E, 2^(E+1)) the posits are therefore evenly spaced, with
n - 1 - regime_length - es fraction bits, and rounding to the nearest
posit, ties to even, is rounding the f32 significand to that many bits.
That holds while the regime leaves room for every exponent bit; for
posit16 es=2 that is 2^-48 <= |x| < 2^48, far outside anything a model
here holds.  Values outside that range raise instead of being rounded
by a rule this module does not implement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def round_to_posit(x, n: int, es: int):
    """x (float32) -> the nearest posit<n, es> value, as float32."""
    x = jnp.asarray(x, jnp.float32)
    a = jnp.abs(x)
    safe = jnp.where(a > 0, a, 1.0)
    e = jnp.floor(jnp.log2(safe)).astype(jnp.int32)
    # log2 of an f32 can land one binade off right at a power of two
    e = jnp.where(jnp.ldexp(1.0, e) > safe, e - 1, e)
    e = jnp.where(jnp.ldexp(1.0, e + 1) <= safe, e + 1, e)
    k = jnp.floor_divide(e, 1 << es)
    regime_len = jnp.where(k >= 0, k + 2, 1 - k)
    frac_bits = n - 1 - regime_len - es
    ulp_exp = e - frac_bits
    q = jnp.round(jnp.ldexp(safe, -ulp_exp))          # half to even
    r = jnp.ldexp(q, ulp_exp)
    return jnp.where(a > 0, jnp.sign(x) * r, 0.0).astype(jnp.float32)


def domain_ok(x, n: int, es: int):
    """True where round_to_posit is exact by the rule above."""
    a = jnp.abs(jnp.asarray(x, jnp.float32))
    lim = (n - 1 - es - 2) << es       # regime that leaves 0 fraction bits
    return (a == 0) | ((a >= 2.0 ** -lim) & (a < 2.0 ** lim))


def checked_round(x, n: int, es: int):
    """round_to_posit, with a count of the values outside its domain."""
    return round_to_posit(x, n, es), jnp.sum(~domain_ok(x, n, es))


round_jit = jax.jit(round_to_posit, static_argnums=(1, 2))
