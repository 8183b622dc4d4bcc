"""Posit-compressed collectives — the paper's number format as a gradient
wire format (beyond-paper distributed-optimization trick, DESIGN.md §5).

A ring all-reduce is reduce-scatter + all-gather, each moving ~N bytes per
chip.  Summation must stay f32 (posit8/16 addition of many shards would
round pathologically), but the *all-gather half carries final values* and
tolerates posit quantization: encode the reduced shard to posit16/8, gather
ints, decode locally.

    allreduce_bytes(f32)            ~ 2 * 4N
    reduce_scatter f32 + gather p16 ~ 4N + 2N   (-25%)
    ... + gather p8                 ~ 4N + 1N   (-37.5%)

Across the pod axis (the slow inter-pod links) gradients are *pre-reduced*
in-pod in f32, so only the compressed cross-pod exchange touches DCN:
cross-pod bytes drop 2x/4x — visible in the dry-run HLO collective sizes
(EXPERIMENTS.md §Perf).

These run inside shard_map; gradient summation correctness is preserved
(quantization error enters once, after the exact f32 reduction, bounded by
the posit RNE half-ulp — measured in tests/test_collectives.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.convert import f32_to_posit
from repro.core.decode import decode_to_f32
from repro.core.types import PositConfig


def compressed_psum(x: jnp.ndarray, axis_name: str, cfg: PositConfig):
    """All-reduce of x over `axis_name` with a posit-compressed gather half.

    Call inside shard_map.  x: any float array, identical shape per member.
    Returns the (quantized) mean-preserving sum on every member.
    """
    n = jax.lax.psum(1, axis_name)
    size = x.size
    pad = (-size) % n
    flat = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, pad))
    shards = flat.reshape(n, size // n if pad == 0 else (size + pad) // n)
    # exact f32 reduction of my shard (reduce-scatter half)
    idx = jax.lax.axis_index(axis_name)
    mine = jax.lax.psum_scatter(shards, axis_name, scatter_dimension=0,
                                tiled=False)
    # compressed all-gather half: posit wire format
    wire = f32_to_posit(mine, cfg)
    gathered = jax.lax.all_gather(wire, axis_name, axis=0, tiled=False)
    out = decode_to_f32(gathered, cfg).reshape(-1)[:size]
    del idx
    return out.reshape(x.shape).astype(x.dtype)


def compressed_grad_sync(grads, axis_name: str, cfg: PositConfig | None):
    """Apply compressed_psum leaf-wise to a gradient pytree (or plain psum
    when cfg is None — the f32 baseline)."""
    if cfg is None:
        return jax.lax.psum(grads, axis_name)
    return jax.tree_util.tree_map(
        lambda g: compressed_psum(g, axis_name, cfg), grads)


# --------------------------------------------------------------------------
# tensor-parallel serving context (used inside the sharded paged step)
# --------------------------------------------------------------------------
# The sharded serving step (serving.engine._sharded_paged_step) runs the
# whole forward inside one shard_map with Megatron column/row-parallel
# weights (distributed.sharding.serving_param_pspecs).  The model blocks
# need two pieces of information the param tree cannot carry: the TP axis
# name (for the one psum each block owes after its row-parallel output
# projection) and whether the vocab dimension is sharded (the embedding
# lookup becomes masked-local + psum, and sampling must reduce across vocab
# shards).  Both travel through this thread-local context, active only
# while the step body is being traced — training and single-device serving
# never see it.
import contextlib
import dataclasses
import threading

_TP = threading.local()


@dataclasses.dataclass(frozen=True)
class TPContext:
    axis: str                       # mesh axis name ("model")
    size: int                       # static axis size
    vocab_sharded: bool             # embed/unembed tables vocab-parallel?
    compress: PositConfig | None    # posit wire format for block psums


@contextlib.contextmanager
def tensor_parallel(axis: str, size: int, vocab_sharded: bool = False,
                    compress: PositConfig | None = None):
    prev = getattr(_TP, "ctx", None)
    _TP.ctx = TPContext(axis, size, vocab_sharded, compress) \
        if size > 1 else None
    try:
        yield
    finally:
        _TP.ctx = prev


def tp_ctx() -> TPContext | None:
    return getattr(_TP, "ctx", None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _psum_g(x, axis: str, compress):
    if compress is not None:
        return compressed_psum(x, axis, compress)
    return jax.lax.psum(x, axis)


def _psum_g_fwd(x, axis: str, compress):
    return _psum_g(x, axis, compress), None


def _psum_g_bwd(axis: str, compress, _, g):
    return (g,)


_psum_g.defvjp(_psum_g_fwd, _psum_g_bwd)


def block_psum(x):
    """The one all-reduce a row-parallel block output owes under TP —
    Megatron's g-operator: psum forward, *identity* backward.

    The identity backward is load-bearing for training: under shard_map
    with check_vma=False, autodiff transposes a raw lax.psum to another
    psum, so a replicated cotangent flowing into the block output would
    multiply by the axis size at every block.  The block's output cotangent
    is already replicated (everything downstream of the psum is replicated
    compute), so the correct pullback is the identity — block_grad_sync at
    the block *entry* is where the one real backward psum happens.

    Identity outside a tensor_parallel context.  With a compress format the
    gather half of the psum moves posit ints instead of f32 (profitable on
    slow inter-chip links, at the cost of the half-ulp wire quantization —
    serving keeps it off by default to preserve single-device bit-parity).
    """
    ctx = tp_ctx()
    if ctx is None:
        return x
    return _psum_g(x, ctx.axis, ctx.compress)


# --------------------------------------------------------------------------
# Megatron f-operator: the training-side dual of block_psum
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_psum(x, axis: str):
    return x


def _grad_psum_fwd(x, axis: str):
    return x, None


def _grad_psum_bwd(axis: str, _, g):
    return (jax.lax.psum(g, axis),)


_grad_psum.defvjp(_grad_psum_fwd, _grad_psum_bwd)


def block_grad_sync(x):
    """Megatron's f-operator at a TP block *entry*: identity forward, psum
    over the TP axis backward.

    A column/row-parallel block consumes a replicated activation and its
    backward produces a partial d(input) per shard (each shard only saw its
    weight slice); the psum here restores the full gradient so everything
    upstream (embeddings, earlier blocks' row-parallel outputs) sees the
    same replicated cotangent on every member.  Identity outside a
    tensor_parallel context — serving never differentiates, so block_psum
    stays the only collective the forward pays.
    """
    ctx = tp_ctx()
    if ctx is None:
        return x
    return _grad_psum(x, ctx.axis)


def sharded_argmax(logits: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Global greedy token ids from vocab-sharded logits [B, V/ntp].

    Each member reduces its local shard to (max, argmax) and only the
    O(B) pairs cross the mesh — never the [B, vocab] logits.  Ties break
    to the lowest global index (vocab order == shard order, and argmax
    picks the first occurrence at both levels), so the result is exactly
    jnp.argmax of the unsharded logits.
    """
    local_v = logits.shape[-1]
    off = jax.lax.axis_index(axis_name) * local_v
    lmax = logits.max(axis=-1)                               # [B]
    larg = jnp.argmax(logits, axis=-1).astype(jnp.int32) + off
    gmax = jax.lax.all_gather(lmax, axis_name)               # [ntp, B]
    garg = jax.lax.all_gather(larg, axis_name)               # [ntp, B]
    shard = jnp.argmax(gmax, axis=0)                         # first max wins
    return jnp.take_along_axis(garg, shard[None, :], axis=0)[0]


def gather_vocab_shards(logits: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """[B, V/ntp] vocab-sharded logits -> full [B, V] on every member (the
    temperature-sampling path; greedy uses sharded_argmax and stays O(B))."""
    return jax.lax.all_gather(logits, axis_name, axis=1, tiled=True)


def cross_pod_grad_sync(grads, cfg: PositConfig | None, mesh,
                        in_specs, data_axis: str = "data",
                        pod_axis: str = "pod"):
    """Two-level gradient sync for the multi-pod mesh: exact f32 psum over
    the in-pod data axis, posit-compressed psum across pods (slow links).

    grads must already be laid out per `in_specs`; runs one shard_map.
    """

    def sync(g):
        g = jax.lax.psum(g, data_axis)                  # fast in-pod links, f32
        return compressed_grad_sync(g, pod_axis, cfg)   # slow links, posit wire

    return jax.shard_map(sync, mesh=mesh, in_specs=in_specs,
                         out_specs=in_specs, check_vma=False)(grads)
