"""Loss and train step — the function the dry-run lowers for train_4k.

Next-token cross-entropy (encoder-only archs train on masked-frame
classification over the same label layout — synthetic targets), MoE aux
loss folded in, AdamW update, metrics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.transformer import ModelConfig, forward
from repro.optim import adamw

AUX_WEIGHT = 0.01


def _token_nll(logits, labels):
    """-log p(label) without materializing log_softmax (shard-friendly:
    logsumexp and an iota-compare masked reduce both respect a vocab-sharded
    last dim; no gather collectives)."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    onehot_sum = jnp.sum(
        jnp.where(jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
                  == labels[..., None], lg, 0.0), axis=-1)
    return lse - onehot_sum


LM_HEAD_CHUNK = 512


def _chunked_lm_head_nll(hidden, labels, params, cfg: ModelConfig):
    """Mean NLL with the LM head evaluated per sequence chunk (remat'd):
    the (B, S, vocab) logits tensor never exists at full length — §Perf
    iteration A3 (chunked cross-entropy)."""
    from repro.models import blocks as B
    Bsz, S, _ = hidden.shape
    c = min(LM_HEAD_CHUNK, S)
    pad = (-S) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    n = (S + pad) // c
    hc = hidden.reshape(Bsz, n, c, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(Bsz, n, c).transpose(1, 0, 2)
    valid = (jnp.arange(S + pad) < S).reshape(n, c)

    def chunk_nll(args):
        h, lab, v = args
        if cfg.tie_embeddings:
            logits = B.unembed(h, params["embed"], cfg.policy)
        else:
            logits = B.linear(h, params["unembed"], cfg.policy)
        nll = _token_nll(logits, lab)
        return jnp.sum(nll * v[None, :])

    sums = jax.lax.map(
        jax.checkpoint(chunk_nll,
                       policy=jax.checkpoint_policies.nothing_saveable),
        (hc, lc, valid))
    return sums.sum() / (Bsz * S)


def lm_loss(params, cfg: ModelConfig, batch):
    tokens = batch.get("tokens")
    if cfg.encoder_only:
        # masked-frame objective stand-in: embeddings in, per-frame classes out
        embeds = batch["embeds"]
        labels = batch["labels"]
        hidden, aux, _ = forward(params, cfg, inputs_embeds=embeds,
                                 return_hidden=True)
        nll = _chunked_lm_head_nll(hidden, labels, params, cfg)
        return nll + AUX_WEIGHT * aux, {}
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    kwargs = {}
    if cfg.input_mode == "tokens+image":
        kwargs["inputs_embeds"] = batch["image_embeds"]
    hidden, aux, _ = forward(params, cfg, tokens=inputs,
                             return_hidden=True, **kwargs)
    # VLM: image positions prepended — score only the token tail
    hidden = hidden[:, -inputs.shape[1]:]
    nll = _chunked_lm_head_nll(hidden, labels, params, cfg)
    return nll + AUX_WEIGHT * aux, {"nll": nll}


def _compute_grads(params, batch, cfg: ModelConfig, accum_steps: int):
    """(loss, metrics, grads) for one (micro-accumulated) batch.

    accum_steps > 1: gradient accumulation over microbatches (sequential
    lax.scan) — activation memory scales 1/accum_steps at identical math,
    the standard fit lever for >=100B models on 16 GB chips (§Perf A2).
    """
    if accum_steps == 1:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, batch), has_aux=True)(params)
        return loss, metrics, grads
    micro = jax.tree_util.tree_map(
        lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                            *x.shape[1:]), batch)

    def acc(carry, mb):
        g_acc, l_acc = carry
        (l, _), g = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, mb), has_aux=True)(params)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        return (g_acc, l_acc + l), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (grads, loss_sum), _ = jax.lax.scan(
        acc, (zeros, jnp.zeros((), jnp.float32)), micro)
    inv = 1.0 / accum_steps
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
    return loss_sum * inv, {}, grads


def _poison_grads(grads, poison):
    """Chaos hook: where `poison` (traced bool scalar) is set, replace
    every gradient leaf with NaN — what a posit NaR entering the gradient
    stream decodes to — so adamw's non-finite guard trips.  A per-leaf
    where-select, so poison=False keeps the gradients bit-identical."""
    return jax.tree_util.tree_map(
        lambda g: jnp.where(poison, jnp.asarray(jnp.nan, g.dtype), g), grads)


def train_step(params, opt_state, batch, cfg: ModelConfig,
               opt_cfg: adamw.OptConfig, accum_steps: int = 1,
               poison=None):
    """One optimization step.  Pure; jit/pjit-able."""
    loss, metrics, grads = _compute_grads(params, batch, cfg, accum_steps)
    if poison is not None:
        grads = _poison_grads(grads, poison)
    params, opt_state, opt_metrics = adamw.apply_updates(
        params, grads, opt_state, opt_cfg)
    metrics = dict(metrics, loss=loss, **opt_metrics)
    return params, opt_state, metrics


def _mesh_grad_norm(grads, pspecs):
    """Global gradient norm on the ("data","model") mesh, computed inside
    the shard_map body *after* the data-axis sync.

    'model'-sharded leaves hold disjoint slices per member — their squared
    sums psum over the TP axis; replicated leaves (norms, embeddings,
    biases) carry identical full gradients on every member thanks to the
    blocks' f-operator, so a local sum is already global.  A plain
    adamw.global_norm inside the body would miss the TP shards; outside it
    would need fully-gathered grads."""
    sq_local = jnp.zeros((), jnp.float32)
    sq_model = jnp.zeros((), jnp.float32)
    leaves = jax.tree_util.tree_leaves(grads)
    specs = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(leaves) == len(specs), (len(leaves), len(specs))
    for g, s in zip(leaves, specs):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if any(ax is not None and "model" in jax.tree_util.tree_leaves([ax])
               for ax in tuple(s)):
            sq_model = sq_model + sq
        else:
            sq_local = sq_local + sq
    return jnp.sqrt(sq_local + jax.lax.psum(sq_model, "model"))


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig, mesh=None, *,
                    accum_steps: int = 1, donate: bool = True,
                    chaos_nar: bool = False):
    """Build the jitted train step: `step(params, opt_state, batch)` —
    or, with chaos_nar=True, `step(params, opt_state, batch, poison)`
    where `poison` is a bool scalar that NaNs the gradient tree on device
    (the trainer's fault-injection hook; the default build carries no
    poison plumbing at all, so the production step is untouched).

    mesh None — the single-device path: plain jit with params/opt-state
    donated (the two largest buffers alias in place; at 235B+f32 moments a
    non-donated step would hold 3x the resident state during the update).

    mesh — one shard_map over the ("data","model") mesh, the training twin
    of serving's _sharded_paged_step.  Inside the body partitioning is
    manual, so the Pallas kernels (flash fwd/bwd, grouped MoE, posit GEMM
    — none of which carry GSPMD rules) run on shard-local tiles:

      data axis:  pure DP — batch rows shard, grads mean via
          distributed.collectives.compressed_grad_sync (posit wire format
          per cfg.policy.grads; exact f32 psum when unset or ndata == 1).
      model axis: Megatron TP per sharding.train_param_pspecs (column/row-
          parallel weights, replicated embed/unembed — no vocab
          parallelism, so the loss needs no vocab collectives).  The
          blocks' forward psum (block_psum) and backward f-operator
          (block_grad_sync) are the only TP collectives per layer.

    TP training (ntp > 1) is attention/MLP stacks only: MoE router
    gradients and recurrent scan states are partial-per-shard and would
    silently diverge — those archs raise and should train DP/FSDP.
    """
    if mesh is None:
        if chaos_nar:
            def step(params, opt_state, batch, poison):
                return train_step(params, opt_state, batch, cfg, opt_cfg,
                                  accum_steps, poison=poison)
        else:
            def step(params, opt_state, batch):
                return train_step(params, opt_state, batch, cfg, opt_cfg,
                                  accum_steps)
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())

    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import (compressed_grad_sync,
                                               tensor_parallel)
    from repro.distributed import sharding

    ndata, ntp = mesh.shape["data"], mesh.shape["model"]
    if ntp > 1:
        bad = [k for k in cfg.block_pattern if k not in ("attn", "attn_local")]
        if bad or cfg.moe is not None:
            raise NotImplementedError(
                f"TP training (model axis = {ntp}) supports attention/MLP "
                f"stacks only; {cfg.name} has moe={cfg.moe is not None}, "
                f"blocks={bad}.  Use a (ndev, 1) data-parallel mesh.")
    wire = cfg.policy.grads if cfg.policy is not None else None

    def body(pspecs, params, opt_state, batch, poison=None):
        with tensor_parallel("model", ntp):
            loss, metrics, grads = _compute_grads(params, batch, cfg,
                                                  accum_steps)
        if ndata > 1:
            inv = 1.0 / ndata
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            grads = compressed_grad_sync(grads, "data", wire)
            loss = jax.lax.pmean(loss, "data")
            metrics = {k: jax.lax.pmean(v, "data") for k, v in metrics.items()}
        if poison is not None:
            # chaos: the NaN reaches the guard through the grad norm,
            # exactly like a real NaR-poisoned gradient would
            grads = _poison_grads(grads, poison)
        gn = _mesh_grad_norm(grads, pspecs)
        params, opt_state, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, opt_cfg, grad_norm=gn)
        return params, opt_state, dict(metrics, loss=loss, **opt_metrics)

    def _specs(params, opt_state, batch):
        pspecs = sharding.train_param_pspecs(params, mesh)
        ospecs = sharding.opt_state_pspecs(opt_state, pspecs, mesh)
        bspecs = jax.tree_util.tree_map(
            lambda x: P("data") if getattr(x, "ndim", 0) else P(), batch)
        return pspecs, ospecs, bspecs

    def _backfill(opt_state):
        # pre-nar_skips checkpoints: backfill the guard counter so the
        # output opt_state tree (which always carries it) matches out_specs
        opt_state = dict(opt_state)
        opt_state.setdefault("nar_skips", jnp.zeros((), jnp.int32))
        return opt_state

    if chaos_nar:
        def step(params, opt_state, batch, poison):
            opt_state = _backfill(opt_state)
            pspecs, ospecs, bspecs = _specs(params, opt_state, batch)
            return jax.shard_map(
                functools.partial(body, pspecs), mesh=mesh,
                in_specs=(pspecs, ospecs, bspecs, P()),
                out_specs=(pspecs, ospecs, P()),
                check_vma=False,
            )(params, opt_state, batch, poison)
    else:
        def step(params, opt_state, batch):
            opt_state = _backfill(opt_state)
            pspecs, ospecs, bspecs = _specs(params, opt_state, batch)
            return jax.shard_map(
                functools.partial(body, pspecs), mesh=mesh,
                in_specs=(pspecs, ospecs, bspecs),
                out_specs=(pspecs, ospecs, P()),
                check_vma=False,
            )(params, opt_state, batch)

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
