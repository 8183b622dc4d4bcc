"""Mixture-of-Experts block: sort-based routing + grouped posit GEMM on the
Pallas path, with the GShard one-hot capacity dispatch as the jnp oracle.

The GShard formulation (dispatch/combine one-hots, per-expert capacity
slots) moves O(G*Tg*E*C) dense one-hot traffic per layer and — worse for
serving — materializes the **full** [E, d_model, d_ff] expert tensors as
f32 every step even though only top_k of E experts are active (for
qwen3-moe-235b-a22b that is all 128 experts' weights decoded for a top-8
step).  Serving steps on the Pallas path now route by sorting instead:
each token's (token, k) pairs are argsorted by expert id, per-expert
segment offsets feed `kernels.ops.grouped_matmul`
(kernels/grouped_gemm.py), and the
grouped kernel streams only the active experts' posit-packed weight tiles
into VMEM, decoding them in front of the MXU with one f32 accumulator per
group (the PERCIVAL-style quire analogue).  Ragged expert groups are
native, so the capacity zero-padding slots of the one-hot dispatch
disappear; tokens scatter back with their combine weights instead of a
[G,Tg,E,C] comb einsum.

Routing semantics are identical on both paths (and replicated under
expert-parallel TP): top-k over the router softmax, per-dispatch-group
arrival-order capacity positions, overflow drops, and combine weights
renormalized over the *kept* experts only — a token whose sibling expert
overflowed redistributes its mix instead of keeping a stale under-weighted
sum (qwen3-moe; OLMoE's `norm_topk=False` keeps the raw probabilities).
The one-hot implementation survives as the CPU/interpret oracle and
the benchmark baseline.  Training uses the grouped path too: the grouped
custom_vjp supplies dX/dW Pallas kernels, and the shard_map train step
(training/train_step.py) makes partitioning manual, so the old GSPMD
carve-out (one-hot einsums for training) is gone.  DENSE_MOE_FALLBACKS
counts every dense dispatch plus the one-hot path's full-expert posit
decodes; tier-1 asserts neither an engine drain nor a kernel-path train
step adds one.

Under a `tensor_parallel` context (the mesh-sharded serving step) experts
are split over the model axis: routing is computed globally on every
shard, non-local (token, k) pairs drop their combine weight to zero, the
grouped GEMM runs over the shard-local expert slice, and the block's one
`collectives.block_psum` assembles the full mixture.

Used by olmoe-1b-7b (64e top-8) and qwen3-moe-235b-a22b (128e top-8).
Expert tables are the biggest posit-storage win (DESIGN.md §4).
"""
from __future__ import annotations

import collections
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.blocks import _dense_init
from repro.quant.policy import PositPolicy, posit_cast_ste

Params = dict[str, Any]

# trace-time executions of the dense one-shot expert path, keyed by reason.
# "expert-decode" entries mean the full [E, d_model, d_ff] posit expert
# tensors were materialized as f32 — the HBM blow-up the grouped kernel
# exists to kill.  On the Pallas path this must stay untouched (tests
# assert an engine drain adds nothing here); the one-hot path survives as
# the CPU/interpret oracle and the FORCE_DENSE benchmark baseline.
DENSE_MOE_FALLBACKS: collections.Counter = collections.Counter()

# in-process switches for the benchmark legs and tests (mirroring
# ops.FORCE_REFERENCE): FORCE_DENSE pins the GShard one-hot oracle even for
# serving steps on the Pallas path; FORCE_GROUPED pins sort-based routing +
# grouped matmul everywhere — including training-shaped calls, which
# normally keep the one-hot path (see moe_block), and the jnp backend,
# where the matmul itself still dispatches kernel-vs-reference via
# use_pallas (on CPU this measures the routing scheme with the dense
# reference matmul behind it).
FORCE_DENSE = False
FORCE_GROUPED = False


def init_moe(key, d_model: int, d_ff: int, n_experts: int, act: str) -> Params:
    ks = jax.random.split(key, 4)
    glu = act in ("geglu", "swiglu")
    p = {
        "router": _dense_init(ks[0], (d_model, n_experts)),
        "w_up": _dense_init(ks[1], (n_experts, d_model, d_ff)),
        "w_down": _dense_init(ks[2], (n_experts, d_ff, d_model), d_ff ** -0.5),
    }
    if glu:
        p["w_gate"] = _dense_init(ks[3], (n_experts, d_model, d_ff))
    return p


def _maybe_decode(w, policy: PositPolicy, count: str | None = None):
    """Full-tensor f32 view of a (possibly posit) weight — the dense path.
    `count` tags posit materializations in DENSE_MOE_FALLBACKS."""
    from repro.core.array import PositArray
    if isinstance(w, PositArray):
        if count is not None:
            DENSE_MOE_FALLBACKS[count] += 1
        return w.to_f32()
    if w.dtype in (jnp.int8, jnp.int16):
        if count is not None:
            DENSE_MOE_FALLBACKS[count] += 1
        from repro.core.decode import decode_to_f32
        return decode_to_f32(w, policy.weights)
    if policy is not None and policy.weights is not None:
        return posit_cast_ste(w, policy.weights)
    return w


def _grouped_weight(w, policy: PositPolicy):
    """(operand, cfg) for grouped_matmul: posit storage passes through at
    storage width (the kernel decodes tiles in VMEM); float weights apply
    the QAT STE round-trip (f32 values — that is training semantics, not a
    serving decode)."""
    from repro.core.array import PositArray
    if isinstance(w, PositArray):
        return w, None
    if w.dtype in (jnp.int8, jnp.int16):
        return w, policy.weights
    if policy is not None and policy.weights is not None:
        return posit_cast_ste(w, policy.weights), None
    return w, None


def _router_logits(xt, router, policy: PositPolicy):
    """Router projection at storage width: posit router tables route
    through kops.pw_matmul (in-kernel decode on the Pallas path) — this was
    the last remaining per-step f32 decode of a posit weight in the block."""
    from repro.core.array import PositArray
    x32 = xt.astype(jnp.float32)
    if isinstance(router, PositArray):
        from repro.kernels import ops as kops
        return kops.pw_matmul(x32, router)
    if router.dtype in (jnp.int8, jnp.int16):
        from repro.kernels import ops as kops
        return kops.pw_matmul(x32, router, policy.weights)
    if policy is not None and policy.weights is not None:
        router = posit_cast_ste(router, policy.weights)
    return jnp.einsum("gtd,de->gte", x32, router)


def _route(xt, p: Params, *, n_experts: int, top_k: int, cap: int,
           policy: PositPolicy, norm_topk: bool = True):
    """Shared routing math: (probs, gate_idx, onehot, pos, keep, comb_w).

    Identical for the grouped and one-hot paths (and replicated across
    expert-parallel shards, so drop decisions agree everywhere): top-k,
    per-group arrival-order capacity position, and combine weights
    renormalized over the kept experts only — normalizing before the drop
    left overflow victims with a stale under-weighted mix (the pinned
    forced-drop regression in tests/test_moe_grouped.py).  norm_topk False
    (OLMoE) skips the renormalization: the kept pairs weigh their softmax
    probabilities as they are, dropped pairs 0.
    """
    G, gs, _ = xt.shape
    logits = _router_logits(xt, p["router"], policy)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # [G,Tg,k]

    onehot = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.int32)
    if cap >= gs:
        # top-k expert ids are distinct per token, so one expert sees at
        # most gs arrivals per group: cap >= gs means no pair can overflow
        # (the serving setting).  Skip the O(T*k*E) arrival-order cumsum
        # on the decode hot path — XLA cannot prove keep is all-true on
        # its own.  pos stays None; the one-hot oracle recomputes it
        # lazily (it needs slot indices either way).
        pos = None
        keep = jnp.ones(gate_vals.shape, bool)
    else:
        pos = _arrival_positions(onehot)
        keep = pos < cap

    comb_w = gate_vals * keep
    if norm_topk:
        comb_w = comb_w / jnp.maximum(comb_w.sum(axis=-1, keepdims=True),
                                      1e-9)
    return probs, gate_idx, onehot, pos, keep, comb_w


def _arrival_positions(onehot):
    """Per-(token, k) arrival position within its expert's dispatch group
    ([G, Tg, k, E] int one-hot -> [G, Tg, k])."""
    G, gs, top_k, E = onehot.shape
    flat = onehot.reshape(G, gs * top_k, E)
    pos = jnp.cumsum(flat, axis=1) - 1
    return (pos * flat).sum(axis=-1).reshape(G, gs, top_k)


def _ep_ctx(n_experts: int):
    """Expert-parallel view under a tensor_parallel context: (local expert
    count, this shard's first global expert id), or None outside TP."""
    from repro.distributed.collectives import tp_ctx
    ctx = tp_ctx()
    if ctx is None:
        return None
    return n_experts // ctx.size, jax.lax.axis_index(ctx.axis) * (
        n_experts // ctx.size)


def _dispatch_grouped(xt, p: Params, *, n_experts: int, top_k: int, act: str,
                      policy: PositPolicy, gate_idx, comb_w):
    """Sort-based dispatch: argsort (token, k) pairs by expert, grouped
    GEMMs over per-expert segments, weighted scatter-add back to tokens."""
    from repro.kernels import ops as kops
    G, gs, d = xt.shape
    T = G * gs
    S = T * top_k
    x_flat = xt.reshape(T, d).astype(jnp.float32)

    ep = _ep_ctx(n_experts)
    eidx = gate_idx.reshape(S)
    w_flat = comb_w.reshape(S)
    if ep is None:
        E_loc, key = n_experts, eidx
    else:
        E_loc, off = ep
        local = (eidx >= off) & (eidx < off + E_loc)
        # non-local pairs sort past every local segment (sentinel id E_loc);
        # their rows fall outside group_offsets[-1] and come back as zeros
        key = jnp.where(local, eidx - off, E_loc)
        w_flat = w_flat * local

    order = jnp.argsort(key)          # stable: ties keep arrival order
    tok = order // top_k
    x_sorted = jnp.take(x_flat, tok, axis=0)
    counts = jnp.bincount(key, length=E_loc + 1)[:E_loc]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])

    w_up, cfg_up = _grouped_weight(p["w_up"], policy)
    w_down, cfg_down = _grouped_weight(p["w_down"], policy)
    up = kops.grouped_matmul(x_sorted, w_up, offsets, cfg=cfg_up)
    if act in ("geglu", "swiglu"):
        w_gate, cfg_gate = _grouped_weight(p["w_gate"], policy)
        gate = kops.grouped_matmul(x_sorted, w_gate, offsets, cfg=cfg_gate)
        h = (jax.nn.gelu(gate) if act == "geglu"
             else jax.nn.silu(gate)) * up
    else:
        h = jax.nn.gelu(up)
    ye = kops.grouped_matmul(h, w_down, offsets, cfg=cfg_down)   # [S, d]

    wsort = jnp.take(w_flat, order)
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(ye * wsort[:, None])
    return out.reshape(G, gs, d)


def _dispatch_oneshot(xt, p: Params, *, n_experts: int, top_k: int, act: str,
                      policy: PositPolicy, cap: int, gate_idx, pos, keep,
                      comb_w):
    """GShard one-hot capacity dispatch — the jnp oracle (and FORCE_DENSE
    benchmark baseline).  Decodes the full expert tensors (counted in
    DENSE_MOE_FALLBACKS when they are posit) and pays the O(G*Tg*E*C)
    dispatch/combine einsums the grouped path removes."""
    G, gs, d = xt.shape
    if pos is None:                       # no-overflow routing skipped it
        pos = _arrival_positions(
            jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.int32))
    ep = _ep_ctx(n_experts)
    if ep is None:
        E_loc = n_experts
        gidx = gate_idx
        width = E_loc
    else:
        E_loc, off = ep
        local = (gate_idx >= off) & (gate_idx < off + E_loc)
        gidx = jnp.where(local, gate_idx - off, E_loc)
        comb_w = comb_w * local
        keep = keep & local
        width = E_loc + 1                 # sentinel column, sliced off below

    onehot = jax.nn.one_hot(gidx, width, dtype=xt.dtype)[..., :E_loc]
    slot_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=xt.dtype)[..., :cap]            # [G,Tg,k,C]
    disp = jnp.einsum("gtke,gtkc->gtec", onehot, slot_oh)
    comb = jnp.einsum("gtke,gtkc,gtk->gtec", onehot.astype(jnp.float32),
                      slot_oh.astype(jnp.float32), comb_w).astype(xt.dtype)

    xe = jnp.einsum("gtec,gtd->gecd", disp, xt)                    # [G,E,C,d]

    w_up = _maybe_decode(p["w_up"], policy, count="expert-decode")
    w_down = _maybe_decode(p["w_down"], policy, count="expert-decode")
    w_gate = _maybe_decode(p["w_gate"], policy, count="expert-decode") \
        if "w_gate" in p else None

    up = jnp.einsum("gecd,edf->gecf", xe, w_up,
                    preferred_element_type=jnp.float32).astype(xt.dtype)
    if act == "geglu":
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xe, w_gate,
                                   preferred_element_type=jnp.float32)
                        .astype(xt.dtype)) * up
    elif act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, w_gate,
                                   preferred_element_type=jnp.float32)
                        .astype(xt.dtype)) * up
    else:
        h = jax.nn.gelu(up)
    ye = jnp.einsum("gecf,efd->gecd", h, w_down,
                    preferred_element_type=jnp.float32).astype(xt.dtype)
    return jnp.einsum("gtec,gecd->gtd", comb, ye)


def moe_block(x, p: Params, *, n_experts: int, top_k: int, act: str,
              policy: PositPolicy, capacity_factor: float | None = 1.25,
              group_size: int = 128, norm_topk: bool = True,
              return_route: bool = False):
    """x [B, S, d] -> (out [B, S, d], aux_loss scalar), and with
    return_route the expert ids each token was routed to ([B, S, top_k]
    int32, global ids also under expert-parallel TP).

    capacity_factor None disables overflow dropping entirely (cap covers
    every (token, k) pair).  Serving steps use this: capacity drops are a
    training-efficiency mechanism, and a per-group cap couples unrelated
    sequences through the decode batch — a token's output would depend on
    which other requests share its step (and bit-parity across data-shard
    layouts would be impossible).

    Dispatch: the Pallas path (use_pallas() — TPU, or the interpret-mode
    tier-1 drive) takes sort-based routing + the grouped posit GEMM for
    serving AND training (the grouped custom_vjp supplies the dX/dW
    kernels, and the training step runs under shard_map where partitioning
    is manual, so the old GSPMD carve-out is gone); the jnp backend keeps
    the GShard one-hot implementation (which is also the oracle).
    REPRO_FORCE_GATHER / ops.FORCE_REFERENCE / FORCE_DENSE pin the one-hot
    path everywhere (benchmark baseline); FORCE_GROUPED pins the grouped
    routing regardless of backend.  With capacity drops (training) the
    grouped dispatch is output-identical to one-hot: comb_w zeroes dropped
    (token, k) pairs before either path combines.
    """
    from repro.kernels import ops as kops
    from repro.distributed.collectives import block_grad_sync
    # f-operator under expert-parallel TP: shard-local expert paths give a
    # partial d(x) per member (identity fwd; see collectives).  Router
    # weight grads stay partial-per-shard though, so the training step
    # rejects MoE with ntp > 1 — this keeps d(x) correct for serving-style
    # grad probes and future EP training.
    x = block_grad_sync(x)
    B, S, d = x.shape
    T = B * S
    gs = min(group_size, T)
    G = T // gs
    # require T % gs == 0 (shapes here are powers of two; enforced by configs)
    xt = x.reshape(G, gs, d)
    if capacity_factor is None:
        # every pair fits (top-k ids are distinct, so an expert receives
        # at most gs arrivals per group): no drops
        cap = gs
    else:
        cap = max(1, int(capacity_factor * gs * top_k / n_experts))

    probs, gate_idx, onehot, pos, keep, comb_w = _route(
        xt, p, n_experts=n_experts, top_k=top_k, cap=cap, policy=policy,
        norm_topk=norm_topk)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e — computed from
    # the global routing, so it is replicated under expert-parallel TP
    f = onehot.astype(jnp.float32).sum(axis=(0, 1, 2)) / (T * top_k)
    pm = probs.mean(axis=(0, 1))
    aux = n_experts * jnp.sum(f * pm)

    # Grouped dispatch is the hot path for serving AND training on the
    # Pallas backend.  Both sharded steps (serving engine, train step) run
    # under shard_map where partitioning is manual and shard-local, so
    # pallas_call's lack of GSPMD rules no longer forces a training
    # carve-out — the grouped custom_vjp's dX/dW kernels carry the
    # backward.  With capacity drops the result is identical to one-hot
    # (comb_w is already zero for dropped pairs).
    # FORCE_DENSE / REPRO_FORCE_GATHER / ops.FORCE_REFERENCE always win
    # (the documented pin-the-oracle-everywhere contract), even over a
    # stale FORCE_GROUPED left set by an earlier in-process experiment
    grouped = ((FORCE_GROUPED or kops.use_pallas())
               and not kops.force_reference() and not FORCE_DENSE)
    if grouped:
        out = _dispatch_grouped(xt, p, n_experts=n_experts, top_k=top_k,
                                act=act, policy=policy, gate_idx=gate_idx,
                                comb_w=comb_w)
    else:
        # counted even for float weights: a zero-delta assertion on this
        # counter is the "the kernel path actually engaged" check for
        # training steps (posit materializations add "expert-decode" too)
        DENSE_MOE_FALLBACKS[
            "forced" if kops.use_pallas() else "jnp-reference"] += 1
        out = _dispatch_oneshot(xt, p, n_experts=n_experts, top_k=top_k,
                                act=act, policy=policy, cap=cap,
                                gate_idx=gate_idx, pos=pos, keep=keep,
                                comb_w=comb_w)
    # under expert-parallel TP each shard holds its experts' partial mix;
    # the block's one psum assembles the full output (identity otherwise)
    from repro.distributed.collectives import block_psum
    out = block_psum(out).reshape(B, S, d).astype(x.dtype)
    if return_route:
        return out, aux, gate_idx.reshape(B, S, top_k)
    return out, aux
