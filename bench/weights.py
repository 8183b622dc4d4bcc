"""Random weights from the seed, in the layout the program's decoder LM takes.

Every leaf is drawn from its own key, `fold_in(seed key, crc32(path))`, and
each layer of a stacked leaf from `fold_in(leaf key, layer)`, so the
reference can make any one layer again without making the others.  Norm
scales are 1 + 0.1 N(0, 1), matrices N(0, 1) / sqrt(fan_in), the
embedding N(0, 1) / sqrt(d_model), which keeps tied-unembedding logits
near unit scale.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_specs(m: dict) -> dict:
    """{path: (per-layer shape, layered, kind)} for config model block `m`.

    Paths are the program's parameter paths (`scanned/0/...` is the
    layer stack); kind is `scale`, `matrix` or `embed`."""
    d, hd = m["d_model"], m["head_dim"]
    H, KV, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    s = {"embed/table": ((m["vocab"], d), False, "embed"),
         "ln_f/scale": ((d,), False, "scale"),
         "scanned/0/ln1/scale": ((d,), True, "scale"),
         "scanned/0/ln2/scale": ((d,), True, "scale"),
         "scanned/0/attn/wq/w": ((d, H * hd), True, "matrix"),
         "scanned/0/attn/wk/w": ((d, KV * hd), True, "matrix"),
         "scanned/0/attn/wv/w": ((d, KV * hd), True, "matrix"),
         "scanned/0/attn/wo/w": ((H * hd, d), True, "matrix"),
         "scanned/0/mlp/w_up/w": ((d, ff), True, "matrix"),
         "scanned/0/mlp/w_gate/w": ((d, ff), True, "matrix"),
         "scanned/0/mlp/w_down/w": ((ff, d), True, "matrix")}
    return s


def _draw(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * z
    return z * (shape[-2] ** -0.5 if kind == "matrix" else shape[-1] ** -0.5)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def make_leaf(key, path: str, spec, layer: int | None = None):
    """One leaf (or, with `layer`, one layer of a stacked leaf)."""
    shape, layered, kind = spec
    k = leaf_key(key, path)
    if layer is not None:
        return _draw(jax.random.fold_in(k, layer), shape, kind)
    return _draw(k, shape, kind)


def make_flat(key, m: dict) -> dict:
    """{path: f32 array} for the whole model (stacked leaves [L, ...])."""
    out = {}
    for path, spec in leaf_specs(m).items():
        if spec[1]:
            out[path] = jnp.stack([make_leaf(key, path, spec, l)
                                   for l in range(m["n_layers"])])
        else:
            out[path] = make_leaf(key, path, spec)
    return out


def nest(flat: dict) -> dict:
    """{`a/b/c`: x} -> the program's nested tree; `scanned` and `rem`
    are tuples, as the program's layer scan takes them."""
    tree: dict = {}
    for path, x in flat.items():
        parts = path.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    if "scanned" in tree:
        st = tree["scanned"]
        tree["scanned"] = tuple(st[str(i)] for i in range(len(st)))
    tree.setdefault("rem", ())
    return tree
