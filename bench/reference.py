"""The plain reference: the configuration's decoder LM in straightforward
jax.numpy, written from the configuration file alone.

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed (bench/weights.py), posit rounding from
bench/posit_ref.py.  It follows what the configuration states:

  x = posit(embed)[tokens]
  per layer:  h = rms(x) * ln1;  q, k, v = h @ posit(Wq|Wk|Wv)
              q, k rotated (RoPE, half split);  k, v rounded to the KV posit
              x += causal_softmax(q k^T / sqrt(hd)) v @ posit(Wo)
              h = rms(x) * ln2
              x += (silu(h @ posit(Wg)) * (h @ posit(Wu))) @ posit(Wd)
  logits = (rms(x) * ln_f) @ posit(embed)^T        (tied embedding)

Matrix products run in f32 at "highest" precision, or, for the control,
with bfloat16 operands and f32 accumulation: the nearest precision below.
Sequences are run one layer at a time over the whole sample, so the
weights of one layer at a time are alive.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.posit_ref import checked_round

PRECISIONS = ("highest", "bf16")


def _mm(precision: str):
    def mm(spec, a, b):
        if precision == "bf16":
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, heads, hd], positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(m, precision, lw, x):
    """One layer over one sequence x [S, d]; lw: this layer's weights."""
    mm = _mm(precision)
    kvn, kves = m["kv_posit"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    S = x.shape[0]
    h = _rms(x, lw["ln1/scale"], m["rms_norm_eps"])
    q = mm("sd,do->so", h, lw["attn/wq/w"]).reshape(S, H, hd)
    k = mm("sd,do->so", h, lw["attn/wk/w"]).reshape(S, KV, hd)
    v = mm("sd,do->so", h, lw["attn/wv/w"]).reshape(S, KV, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, bad_k = checked_round(k, kvn, kves)
    v, bad_v = checked_round(v, kvn, kves)
    G = H // KV
    qg = q.reshape(S, KV, G, hd)
    s = mm("qkgd,tkd->kgqt", qg, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("kgqt,tkd->qkgd", p, v).reshape(S, H * hd)
    x = x + mm("so,od->sd", o, lw["attn/wo/w"])
    h = _rms(x, lw["ln2/scale"], m["rms_norm_eps"])
    g = mm("sd,df->sf", h, lw["mlp/w_gate/w"])
    u = mm("sd,df->sf", h, lw["mlp/w_up/w"])
    x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, lw["mlp/w_down/w"])
    return x, bad_k + bad_v


def _round_weight(m, path, x):
    """Every matrix and the embedding are stored in posit; norm scales stay
    f32."""
    if path.endswith("scale"):
        return x, jnp.int32(0)
    n, es = m["weight_posit"]
    return checked_round(x, n, es)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(mkey, precision, key, layer, X):
    m = dict(mkey)
    specs = W.leaf_specs(m)
    lw, bad = {}, jnp.int32(0)
    for path, spec in specs.items():
        if spec[1]:
            short = path.split("/", 2)[2]
            lw[short], b = _round_weight(m, path, W.make_leaf(key, path, spec,
                                                              layer))
            bad = bad + b
    out, b = jax.lax.map(lambda x: _block(m, precision, lw, x), X)
    return out, bad + b.sum()


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(mkey, key, tokens):
    m = dict(mkey)
    path = "embed/table"
    table, bad = _round_weight(m, path, W.make_leaf(
        key, path, W.leaf_specs(m)[path]))
    return table[tokens], bad


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(mkey, precision, key, X, pick, valid):
    """Per position: (reference's best logit - logit of token `pick`,
    argmax).  valid marks the positions compared."""
    m = dict(mkey)
    mm = _mm(precision)
    specs = W.leaf_specs(m)
    table, bad = _round_weight(m, "embed/table", W.make_leaf(
        key, "embed/table", specs["embed/table"]))
    ln_f = W.make_leaf(key, "ln_f/scale", specs["ln_f/scale"])

    def one(args):
        x, tok, ok = args
        logits = mm("sd,vd->sv", _rms(x, ln_f, m["rms_norm_eps"]), table)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
        return jnp.where(ok, best - got, 0.0), jnp.argmax(logits, -1)

    gaps, top = jax.lax.map(one, (X, pick, valid))
    return gaps, top, bad


def model_key(m: dict):
    """The hashable view of the configuration's model block that the
    reference's jitted functions are keyed on."""
    keep = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "rope_theta",
            "rms_norm_eps", "weight_posit", "kv_posit")
    return tuple((k, tuple(m[k]) if isinstance(m[k], list) else m[k])
                 for k in keep if k in m)


def teacher_forced(m: dict, seed: int, seqs, first: list[int], length: int,
                   precision: str = "highest", picks=None):
    """Run the reference over each sequence (prompt + served tokens, padded
    to `length`) and return, per sequence, the gap of each served token
    below the reference's best logit ([n_served] float), the reference's
    own argmax at those positions, and the count of values outside the
    posit rounding's domain (0 when the comparison is sound).

    first[i]: the position whose logits produce sequence i's first served
    token (len(prompt) - 1).  picks[i], if given, are the tokens whose gaps
    are read at those positions instead of the served ones."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    mk = model_key(m)
    key = W.seed_key(seed)
    n = len(seqs)
    tokens = np.zeros((n, length), np.int32)
    valid = np.zeros((n, length), bool)
    pick = np.zeros((n, length), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > length:
            raise ValueError(f"sequence of {len(s)} tokens > {length}")
        tokens[i, :len(s)] = s
        valid[i, first[i]:len(s) - 1] = True
        pick[i, :len(s) - 1] = s[1:]
        if picks is not None:
            pick[i, first[i]:len(s) - 1] = picks[i]
    X, bad = _embed(mk, key, jnp.asarray(tokens))
    for layer in range(m["n_layers"]):
        X, b = _layer(mk, precision, key, layer, X)
        bad = bad + b
    gaps, top, b = _head(mk, precision, key, X, jnp.asarray(pick),
                         jnp.asarray(valid))
    gaps, top, bad = jax.device_get((gaps, top, bad + b))
    cut = [slice(first[i], len(s) - 1) for i, s in enumerate(seqs)]
    return ([gaps[i, c] for i, c in enumerate(cut)],
            [top[i, c] for i, c in enumerate(cut)], int(bad))


def control_gaps(m: dict, seed: int, seqs, first: list[int], length: int):
    """The control, the reference put in the program's place one precision
    lower: at each position of the same sequences, the gap below the
    "highest" reference's best logit of the token that the bfloat16
    reference puts first."""
    _, tops, bad_lo = teacher_forced(m, seed, seqs, first, length, "bf16")
    gaps, _, bad = teacher_forced(m, seed, seqs, first, length,
                                     "highest", picks=tops)
    return gaps, bad + bad_lo
