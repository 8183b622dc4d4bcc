"""Model assembly: decoder LMs, encoder-only stacks, MoE, SSM and hybrid
patterns — one config-driven implementation covering all ten assigned
architectures (DESIGN.md §4).

Layers are grouped by the repeating `block_pattern` and scanned
(jax.lax.scan over stacked parameters) so even the 94-layer MoE lowers to a
compact HLO; each scanned step is rematerialized (configurable policy).
Posit enters through cfg.policy (see quant/policy.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models import griffin as GR
from repro.models import moe as MOE
from repro.models import rwkv6 as RW
from repro.quant.policy import NONE, PositPolicy

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    # capacity/dispatch group: routing drops overflow per `group_size`
    # tokens on both MoE paths (models/moe.py — sort-based grouped GEMM on
    # the Pallas path, GShard one-hot as the jnp oracle)
    group_size: int = 128
    # renormalize the kept top-k router probabilities to sum to one
    # (qwen3-moe); False uses them as they are (OLMoE, norm_topk_prob
    # false)
    norm_topk: bool = True


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    act: str = "swiglu"
    qkv_bias: bool = False
    qk_norm: bool = False             # RMSNorm over the full q and k widths
    rope_theta: float = 10000.0
    norm: str = "rmsnorm"
    encoder_only: bool = False
    block_pattern: tuple[str, ...] = ("attn",)
    window: int | None = None         # for "attn_local"
    moe: MoEConfig | None = None
    tie_embeddings: bool = True
    embed_scale: bool = False         # gemma: x *= sqrt(d_model)
    input_mode: str = "tokens"        # tokens | embeddings | tokens+image
    dtype: str = "float32"
    policy: PositPolicy = NONE
    remat: bool = True
    scan_layers: bool = True          # False: unrolled (cost-probe mode)
    rwkv_head_dim: int = 64

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def pattern_reps(self) -> int:
        return self.n_layers // len(self.block_pattern)

    @property
    def pattern_rem(self) -> int:
        return self.n_layers % len(self.block_pattern)

    def param_count(self) -> int:
        """Analytic parameter count (for roofline MODEL_FLOPS)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        per_layer = {}
        glu = 3 if self.act in ("geglu", "swiglu") else 2
        attn = d * self.hd * (self.n_heads + 2 * self.n_kv) + self.n_heads * self.hd * d
        if self.qk_norm:
            attn += self.hd * (self.n_heads + self.n_kv)
        if self.moe:
            mlp = d * self.moe.n_experts + self.moe.n_experts * glu * d * ff
        else:
            mlp = glu * d * ff
        per_layer["attn"] = attn + mlp
        per_layer["attn_local"] = attn + mlp
        per_layer["rwkv6"] = 6 * d * d + 2 * d * ff + d * RW.DECAY_LORA * 2
        per_layer["rglru"] = 5 * d * d + mlp
        total = 0
        for i in range(self.n_layers):
            total += per_layer[self.block_pattern[i % len(self.block_pattern)]]
        total += v * d * (1 if self.tie_embeddings else 2)
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of n_experts)."""
        if not self.moe:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        glu = 3 if self.act in ("geglu", "swiglu") else 2
        dense = self.param_count()
        moe_all = self.n_layers * self.moe.n_experts * glu * d * ff
        moe_active = self.n_layers * self.moe.top_k * glu * d * ff
        return dense - moe_all + moe_active


# --------------------------------------------------------------------------
# per-block init/apply
# --------------------------------------------------------------------------
def _init_block(key, kind: str, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    norm_init = (B.init_rmsnorm if cfg.norm == "rmsnorm"
                 else B.init_layernorm)
    if kind in ("attn", "attn_local"):
        p = {"ln1": norm_init(cfg.d_model), "ln2": norm_init(cfg.d_model),
             "attn": B.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                      cfg.n_kv, cfg.hd, cfg.qkv_bias,
                                      cfg.qk_norm)}
        if cfg.moe:
            p["moe"] = MOE.init_moe(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.moe.n_experts, cfg.act)
        else:
            p["mlp"] = B.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act)
        return p
    if kind == "rwkv6":
        return {"ln1": norm_init(cfg.d_model), "ln2": norm_init(cfg.d_model),
                "tmix": RW.init_rwkv6(ks[0], cfg.d_model, cfg.rwkv_head_dim),
                "cmix": RW.init_rwkv6_channel_mix(ks[1], cfg.d_model, cfg.d_ff)}
    if kind == "rglru":
        p = {"ln1": norm_init(cfg.d_model), "ln2": norm_init(cfg.d_model),
             "rec": GR.init_rglru_block(ks[0], cfg.d_model)}
        if cfg.moe:
            p["moe"] = MOE.init_moe(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.moe.n_experts, cfg.act)
        else:
            p["mlp"] = B.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act)
        return p
    raise ValueError(kind)


def _norm(x, p, cfg: ModelConfig):
    from repro.distributed.sharding import shard_activation
    h = (B.rms_norm(x, p) if cfg.norm == "rmsnorm"
         else B.layer_norm(x, p))
    # Megatron-SP: blocks consume sequence-gathered activations (no-op
    # outside a mesh context / under fsdp) — §Perf iteration A4
    return shard_activation(h, "block_in")


def _moe(h, p: Params, cfg: ModelConfig, capacity_factor, record: bool):
    """The MoE block: (out, aux loss, [B, S, top_k] expert ids if `record`
    else None)."""
    res = MOE.moe_block(
        h, p, n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k, act=cfg.act,
        policy=cfg.policy, capacity_factor=capacity_factor,
        group_size=cfg.moe.group_size, norm_topk=cfg.moe.norm_topk,
        return_route=record)
    return res if record else (*res, None)


def _apply_block(x, p: Params, kind: str, cfg: ModelConfig, positions,
                 cache, aux, record: bool = False):
    """One layer: (x, new cache, aux, route), route the [B, S, top_k]
    expert ids of an MoE layer when `record`, else None."""
    pol = cfg.policy
    route = None
    if cfg.moe:
        # serving never drops: a per-group capacity would couple a token's
        # output to the other requests sharing its batch (and break
        # bit-parity across data-shard layouts); None also routes the
        # Pallas path into the grouped GEMM (models/moe.py)
        moe_cf = None if cache is not None else cfg.moe.capacity_factor
    if kind in ("attn", "attn_local"):
        h, new_cache = B.attention_block(
            _norm(x, p["ln1"], cfg), p["attn"], n_heads=cfg.n_heads,
            n_kv=cfg.n_kv, head_dim=cfg.hd, positions=positions, policy=pol,
            causal=not cfg.encoder_only,
            window=cfg.window if kind == "attn_local" else None,
            rope_theta=cfg.rope_theta, kv_cache=cache)
        x = x + h.astype(x.dtype)
        if cfg.moe:
            h, a, route = _moe(_norm(x, p["ln2"], cfg), p["moe"], cfg,
                               moe_cf, record)
            aux = aux + a
        else:
            h = B.mlp_block(_norm(x, p["ln2"], cfg), p["mlp"], act=cfg.act,
                            policy=pol)
        return x + h.astype(x.dtype), new_cache, aux, route
    if kind == "rwkv6":
        if cache is None:                        # training / no-cache path
            h, new_t = RW.rwkv6_time_mix(_norm(x, p["ln1"], cfg), p["tmix"],
                                         head_dim=cfg.rwkv_head_dim,
                                         policy=pol, state=None)
            x = x + h.astype(x.dtype)
            h, new_c = RW.rwkv6_channel_mix(_norm(x, p["ln2"], cfg),
                                            p["cmix"], policy=pol,
                                            last_x=None)
            return x + h.astype(x.dtype), (new_t, new_c), aux, None
        # serving: dense cache tuples or a posit state-pool dict — both run
        # the stateful chunk-invariant path (serving/backends.py)
        from repro.serving import backends as SB
        if isinstance(cache, dict):
            sl, nn = cache["seq_lens"], cache["num_new"]
            S0 = SB.zero_fresh(cache["wkv"], sl)
            tsh = SB.zero_fresh(cache["tshift"], sl)
            csh = SB.zero_fresh(cache["cshift"], sl)
        else:
            (S0, tsh), csh = cache
            nn = None
        h, (S_fin, t_last) = RW.rwkv6_time_mix_serving(
            _norm(x, p["ln1"], cfg), p["tmix"], head_dim=cfg.rwkv_head_dim,
            policy=pol, state=(S0, tsh), num_new=nn)
        x = x + h.astype(x.dtype)
        h, c_last = RW.rwkv6_channel_mix_serving(
            _norm(x, p["ln2"], cfg), p["cmix"], policy=pol, last_x=csh,
            num_new=nn)
        x = x + h.astype(x.dtype)
        if isinstance(cache, dict):
            new_cache = {"wkv": S_fin,
                         "tshift": SB.store_state(cache["tshift"], t_last,
                                                  nn),
                         "cshift": SB.store_state(cache["cshift"], c_last,
                                                  nn),
                         "seq_lens": sl, "num_new": nn}
        else:
            new_cache = ((S_fin, t_last), c_last)
        return x, new_cache, aux, None
    if kind == "rglru":
        if cache is None:                        # training / no-cache path
            h, new_state = GR.rglru_block(_norm(x, p["ln1"], cfg), p["rec"],
                                          policy=pol, state=None)
        else:
            from repro.serving import backends as SB
            if isinstance(cache, dict):
                sl, nn = cache["seq_lens"], cache["num_new"]
                h0 = SB.zero_fresh(cache["h"], sl)
                cv = SB.zero_fresh(cache["conv"], sl)
            else:
                h0, cv = cache
                nn = None
            h, (h_fin, conv_last) = GR.rglru_block_serving(
                _norm(x, p["ln1"], cfg), p["rec"], policy=pol,
                state=(h0, cv), num_new=nn)
            if isinstance(cache, dict):
                new_state = {"h": h_fin,
                             "conv": SB.store_state(cache["conv"], conv_last,
                                                    nn),
                             "seq_lens": sl, "num_new": nn}
            else:
                new_state = (h_fin, conv_last)
        x = x + h.astype(x.dtype)
        if cfg.moe:
            h, a, route = _moe(_norm(x, p["ln2"], cfg), p["moe"], cfg,
                               moe_cf, record)
            aux = aux + a
        else:
            h = B.mlp_block(_norm(x, p["ln2"], cfg), p["mlp"], act=cfg.act,
                            policy=pol)
        return x + h.astype(x.dtype), new_state, aux, route
    raise ValueError(kind)


# --------------------------------------------------------------------------
# cache pytrees
# --------------------------------------------------------------------------
def init_layer_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.float32):
    from repro.serving.kv_cache import init_cache
    if kind == "attn":
        return init_cache(batch, cfg.n_kv, max_len, cfg.hd,
                          cfg.policy.kv_cache, dtype)
    if kind == "attn_local":
        # full-length buffer; a window-sized ring buffer is a §Perf memory
        # optimization applied in the hillclimb (EXPERIMENTS.md)
        return init_cache(batch, cfg.n_kv, max_len, cfg.hd,
                          cfg.policy.kv_cache, dtype)
    if kind == "rwkv6":
        H = cfg.d_model // cfg.rwkv_head_dim
        t = (jnp.zeros((batch, H, cfg.rwkv_head_dim, cfg.rwkv_head_dim), dtype),
             jnp.zeros((batch, cfg.d_model), dtype))
        c = jnp.zeros((batch, cfg.d_model), dtype)
        return (t, c)
    if kind == "rglru":
        return (jnp.zeros((batch, cfg.d_model), jnp.float32),
                jnp.zeros((batch, GR.CONV_WIDTH - 1, cfg.d_model), dtype))
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32):
    """Stacked caches: {kind_position: stacked over reps} + remainder list."""
    P = len(cfg.block_pattern)
    reps = cfg.pattern_reps

    def stack(kind):
        one = init_layer_cache(kind, cfg, batch, max_len, dtype)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (reps,) + x.shape), one)

    scanned = tuple(stack(k) for k in cfg.block_pattern) if reps else ()
    rem = tuple(init_layer_cache(cfg.block_pattern[i], cfg, batch, max_len,
                                 dtype)
                for i in range(cfg.pattern_rem))
    return {"scanned": scanned, "rem": rem}


# ---- paged caches (continuous-batching serving; serving/backends.py) ------
def init_paged_pages(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.float32, max_seqs: int = 0):
    """Per-layer serving pools in the same {scanned, rem} structure as
    init_caches.  Each pattern position gets its backend's pool: paged posit
    KV for attn/attn_local, a fixed-size posit state pool (sized max_seqs)
    for rwkv6/rglru — hybrid patterns mix both side by side."""
    from repro.serving.backends import backend_for
    reps = cfg.pattern_reps

    def one(kind):
        return backend_for(kind, cfg).init_layer(cfg, num_pages, page_size,
                                                 max_seqs, dtype)

    def stack(kind):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (reps,) + x.shape), one(kind))

    scanned = tuple(stack(k) for k in cfg.block_pattern) if reps else ()
    rem = tuple(one(cfg.block_pattern[i]) for i in range(cfg.pattern_rem))
    return {"scanned": scanned, "rem": rem}


def assemble_paged_caches(pages, page_table, seq_lens, num_new):
    """Pools tree + this step's scheduler inputs -> forward()-ready caches.

    The scheduler fields are identical for every layer; scanned groups get
    them broadcast over the stacked reps axis so lax.scan can slice them.
    KV pools additionally take the page table; state pools are slot-indexed
    and just carry seq_lens/num_new."""
    from repro.serving.paged_kv import assemble_layer_cache

    def one(p, stacked: bool):
        if "k_pages" not in p:                    # state-pool layer
            if stacked:
                reps = next(iter(p.values())).shape[0]
                return {**p,
                        "seq_lens": jnp.broadcast_to(
                            seq_lens, (reps,) + seq_lens.shape),
                        "num_new": jnp.broadcast_to(
                            num_new, (reps,) + num_new.shape)}
            return {**p, "seq_lens": seq_lens, "num_new": num_new}
        if stacked:
            reps = p["k_pages"].shape[0]
            return assemble_layer_cache(
                p,
                jnp.broadcast_to(page_table, (reps,) + page_table.shape),
                jnp.broadcast_to(seq_lens, (reps,) + seq_lens.shape),
                jnp.broadcast_to(num_new, (reps,) + num_new.shape))
        return assemble_layer_cache(p, page_table, seq_lens, num_new)

    return {"scanned": tuple(one(p, True) for p in pages["scanned"]),
            "rem": tuple(one(p, False) for p in pages["rem"])}


def copy_paged_pages(pages, src, dst):
    """Copy page `src` onto page `dst` in every KV layer's pools (the device
    half of the prefix cache's copy-on-write: the host rewrites one table
    entry, this duplicates the page contents it pointed at).  src/dst are
    (traced) scalars — shard-local ids when the pools are shard_mapped.
    State-pool layers have no pages and pass through untouched (the prefix
    cache is KV-only)."""
    from repro.serving.paged_kv import copy_layer_pages
    return {"scanned": tuple(copy_layer_pages(p, src, dst, stacked=True)
                             if "k_pages" in p else p
                             for p in pages["scanned"]),
            "rem": tuple(copy_layer_pages(p, src, dst)
                         if "k_pages" in p else p
                         for p in pages["rem"])}


def poison_paged_pages(pages, pg):
    """Overwrite page `pg` with the posit NaR pattern (NaN for float
    pools) in every KV layer — the device half of the chaos harness's
    bit-flipped-page injection (serving/faults.py).  State-pool layers
    pass through untouched, like copy_paged_pages."""
    from repro.serving.paged_kv import poison_layer_pages
    return {"scanned": tuple(poison_layer_pages(p, pg, stacked=True)
                             if "k_pages" in p else p
                             for p in pages["scanned"]),
            "rem": tuple(poison_layer_pages(p, pg)
                         if "k_pages" in p else p
                         for p in pages["rem"])}


def extract_paged_pages(caches):
    """Inverse of assemble_paged_caches: keep only the device-resident
    pools (the scheduler recomputes the rest every step)."""
    from repro.serving.paged_kv import extract_layer_pages

    def one(c):
        if "k_pages" in c:
            return extract_layer_pages(c)
        return {k: v for k, v in c.items()
                if k not in ("seq_lens", "num_new")}

    return {"scanned": tuple(one(c) for c in caches["scanned"]),
            "rem": tuple(one(c) for c in caches["rem"])}


# --------------------------------------------------------------------------
# model init / forward
# --------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig) -> Params:
    P = len(cfg.block_pattern)
    reps, rem = cfg.pattern_reps, cfg.pattern_rem
    keys = jax.random.split(key, reps * P + rem + 2)

    def stacked(pos):
        kind = cfg.block_pattern[pos]
        per_rep = [
            _init_block(keys[r * P + pos], kind, cfg) for r in range(reps)
        ]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_rep)

    params: Params = {
        "embed": B.init_embedding(keys[-1], cfg.vocab, cfg.d_model),
        "ln_f": (B.init_rmsnorm(cfg.d_model) if cfg.norm == "rmsnorm"
                 else B.init_layernorm(cfg.d_model)),
        "scanned": tuple(stacked(i) for i in range(P)) if reps else (),
        "rem": tuple(
            _init_block(keys[reps * P + i], cfg.block_pattern[i], cfg)
            for i in range(rem)),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = B.init_linear(keys[-2], cfg.d_model, cfg.vocab)
    return params


def forward(params: Params, cfg: ModelConfig, *, tokens=None,
            inputs_embeds=None, positions=None, caches=None,
            return_hidden: bool = False, return_routes: bool = False):
    """Returns (logits [B,S,vocab], aux_loss, new_caches).

    tokens [B,S] int32 and/or inputs_embeds [B,Se,d] depending on
    cfg.input_mode.  positions [B,S] absolute positions (default arange,
    offset by cache length when serving).
    return_hidden: skip the unembedding and return the final normalized
    hidden states instead of logits (the chunked-loss training path —
    §Perf iteration A3 — computes the LM head per sequence chunk).
    return_routes: also return, last, the expert ids every token was routed
    to in each MoE layer, [MoE layers, B, S, top_k] int32 in layer order
    (None for a model without MoE layers): the serving engine's routing
    record.
    """
    pol = cfg.policy
    if cfg.input_mode == "embeddings":
        x = inputs_embeds
    elif cfg.input_mode == "tokens+image" and inputs_embeds is not None:
        t = B.embed(tokens, params["embed"], pol)
        x = jnp.concatenate([inputs_embeds.astype(t.dtype), t], axis=1)
    else:
        x = B.embed(tokens, params["embed"], pol)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = x.astype(jnp.dtype(cfg.dtype))
    from repro.distributed.sharding import shard_activation
    x = shard_activation(x, "act")

    Bsz, S, _ = x.shape
    if positions is None:
        off = 0
        if caches is not None:
            off = _cache_length(caches, cfg)
        positions = off + jnp.broadcast_to(jnp.arange(S), (Bsz, S))

    aux = jnp.zeros((), jnp.float32)
    P = len(cfg.block_pattern)
    reps = cfg.pattern_reps

    serving = caches is not None
    scanned_caches = caches["scanned"] if serving else tuple(None for _ in range(P))
    rem_caches = caches["rem"] if serving else tuple(
        None for _ in range(cfg.pattern_rem))

    def superblock(carry, inputs):
        x, aux = carry
        layer_params = inputs[0]
        layer_caches = inputs[1]
        new_caches, routes = [], []
        for pos in range(P):
            kind = cfg.block_pattern[pos]
            cache = layer_caches[pos] if serving else None
            x, nc, aux, route = _apply_block(x, layer_params[pos], kind, cfg,
                                             positions, cache, aux,
                                             return_routes)
            new_caches.append(nc)
            routes.append(route)
        return (x, aux), (tuple(new_caches), tuple(routes))

    new_scanned, scanned_routes = (), ()
    if reps:
        fn = jax.checkpoint(superblock,
                            policy=jax.checkpoint_policies.nothing_saveable) \
            if cfg.remat else superblock
        xs_caches = (scanned_caches if serving
                     else tuple(jnp.zeros((reps,)) for _ in range(P)))
        if cfg.scan_layers:
            (x, aux), (new_scanned, scanned_routes) = jax.lax.scan(
                fn, (x, aux), (params["scanned"], xs_caches))
        else:
            # unrolled python loop: identical math, per-layer ops visible to
            # cost_analysis (the dry-run's trip-count-correct probe mode)
            carry = (x, aux)
            ys = []
            for r in range(reps):
                sl = jax.tree_util.tree_map(lambda t: t[r],
                                            (params["scanned"], xs_caches))
                carry, nc = fn(carry, sl)
                ys.append(nc)
            x, aux = carry
            stack = lambda *z: jnp.stack(z)  # noqa: E731
            if serving:
                new_scanned = jax.tree_util.tree_map(
                    stack, *[nc for nc, _ in ys])
            if return_routes:
                scanned_routes = jax.tree_util.tree_map(
                    stack, *[r for _, r in ys])

    new_rem, rem_routes = [], []
    for i in range(cfg.pattern_rem):
        kind = cfg.block_pattern[i]
        x, nc, aux, route = _apply_block(
            x, params["rem"][i], kind, cfg, positions,
            rem_caches[i] if serving else None, aux, return_routes)
        new_rem.append(nc)
        rem_routes.append(route)

    x = _norm(x, params["ln_f"], cfg)
    new_caches = ({"scanned": new_scanned, "rem": tuple(new_rem)}
                  if serving else None)
    extra = ((_layer_routes(scanned_routes, rem_routes),)
             if return_routes else ())
    if return_hidden:
        return (x, aux, new_caches) + extra
    if cfg.tie_embeddings:
        logits = B.unembed(x, params["embed"], pol)
    else:
        logits = B.linear(x, params["unembed"], pol).astype(jnp.float32)
    logits = shard_activation(logits, "logits")
    return (logits, aux, new_caches) + extra


def _layer_routes(scanned, rem):
    """The MoE layers' expert ids in layer order (scanned layer
    r * len(pattern) + position, then the rest): [layers, B, S, top_k],
    or None when no layer routed."""
    layers = []
    st = [r for r in scanned if r is not None]     # each [reps, B, S, k]
    if st:
        st = jnp.stack(st, axis=1)                  # [reps, P, B, S, k]
        layers.append(st.reshape((-1,) + st.shape[2:]))
    layers += [r[None] for r in rem if r is not None]
    return jnp.concatenate(layers) if layers else None


def _cache_length(caches, cfg: ModelConfig):
    """Current sequence offset from the first attention cache (if any).

    Dense caches: scalar length.  Paged caches: per-sequence seq_lens,
    returned [B, 1] so `off + arange(S)` broadcasts to ragged positions."""
    for group in (caches["scanned"], caches["rem"]):
        for c in group:
            if isinstance(c, dict) and "seq_lens" in c:
                sl = c["seq_lens"]
                sl = sl[0] if sl.ndim == 2 else sl    # unstack scanned reps
                return sl[:, None]
            if isinstance(c, dict) and "length" in c:
                ln = c["length"]
                return ln[0] if ln.ndim else ln
    return 0
