"""The dense llama block (attention, then a SwiGLU MLP, every layer): the
family of a configuration that names none.  It binds the weight maker
(bench/weights.py), the plain reference (bench/reference.py) and the work
counts (bench/work.py) to the family hooks (bench/families/__init__.py).
"""
from __future__ import annotations

import functools

import jax

from bench import reference, work
from bench import weights as W

leaf_specs, make_flat, nest = W.leaf_specs, W.make_flat, W.nest
control_gaps = reference.control_gaps

# On the chip, a Pallas kernel's op in the trace is named after its
# `pallas_call` (as read on one TPU v5e): `posit_gemm.N` (every posit GEMM:
# projections, MLP, unembedding), `paged_flash_decode.N` and
# `paged_flash_prefill.N`.
KERNELS = {
    "gemm": ("posit_gemm.",),
    "attn": ("paged_flash_decode.", "paged_flash_prefill."),
}


def stated(m: dict) -> dict:
    return {"act": m["act"], "tie_embeddings": m["tie_embeddings"],
            "rope_theta": m["rope_theta"], "hd": m["head_dim"],
            "norm": "rmsnorm", "block_pattern": ("attn",),
            "qkv_bias": False, "embed_scale": False,
            "moe": None}


def got(cfg) -> dict:
    return {"act": cfg.act, "tie_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "hd": cfg.hd, "norm": cfg.norm,
            "block_pattern": cfg.block_pattern, "qkv_bias": cfg.qkv_bias,
            "embed_scale": cfg.embed_scale, "moe": cfg.moe}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _serving_params(key, mkey, pcfg):
    from bench.program import quantize_for_serving
    m = dict(mkey)
    return quantize_for_serving(W.nest(W.make_flat(key, m)), pcfg)


def serving_params(seed: int, m: dict, cfg):
    """Random weights from the seed, quantized by the program's PTQ, in one
    jitted call on the device; the f32 tree lives only inside it."""
    return _serving_params(W.seed_key(seed), reference.model_key(m),
                           cfg.policy.weights)


def teacher_forced(m, seed, seqs, first, length, collected=None, **kw):
    """bench/reference.py `teacher_forced`; the dense reference takes
    nothing from the run."""
    return reference.teacher_forced(m, seed, seqs, first, length, **kw)


def collect(engine, sample):
    return None


def extra_checks(m, check, collected) -> dict:
    return {}


def step_work(m, rows, records) -> list[dict]:
    return [work.step_work(m, r) for r in rows]
