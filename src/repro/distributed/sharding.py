"""Sharding rules: params, optimizer state, activations -> PartitionSpecs.

Layout (DESIGN.md §5):
  * FSDP: weight matrices shard their d_model/d_ff "reduction-side" dim over
    ("pod","data") — XLA GSPMD all-gathers per scanned layer, overlapping
    with compute (latency-hiding scheduler flags in launch scripts).
  * TP (Megatron): the "parallel" dim (heads*head_dim, d_ff, vocab) shards
    over "model"; column-parallel in, row-parallel out -> one psum per block.
  * EP: MoE expert dim shards over "model" (experts % 16 == 0 for both MoE
    archs).
  * Dims that do not divide the assigned axes are dropped to replication
    (guard below) — e.g. hubert's vocab=504.

Rules are path-regex -> trailing-dims spec; stacked scan dims get leading
None automatically.
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

FSDP = "__fsdp__"     # placeholder replaced by ("pod","data") or "data"


def strategy_for(cfg, mesh) -> str:
    """Per-arch parallelism strategy (DESIGN.md §5).

    "tp2d": Megatron TP over 'model' + FSDP over data axes.  Requires every
            TP-sharded dim to divide the model-axis size (heads, d_ff,
            d_model, experts).
    "fsdp": pure fully-sharded data parallel — batch and parameters shard
            over the flattened (data, model) axes; right for models whose
            per-layer weight gathers are cheaper than Megatron psums of
            (B*S, d) activations (everything below ~50B here), and for
            head-count-indivisible stacks (smollm, rwkv6).
    """
    tp = mesh.shape["model"]
    ok = cfg.d_model % tp == 0 and cfg.d_ff % tp == 0
    has_attn = any(k in ("attn", "attn_local") for k in cfg.block_pattern)
    if has_attn:
        ok = ok and cfg.n_heads % tp == 0
    else:
        ok = False                     # pure-recurrent stacks: FSDP
    if cfg.moe is not None:
        ok = ok and cfg.moe.n_experts % tp == 0
    # napkin math (EXPERIMENTS.md §Perf): TP psum bytes/layer ~ 8*B*S*d/dp
    # vs FSDP gather bytes/layer ~ 3*layer_params; at 1M-token batches the
    # crossover sits near ~50B params on a (16,16) v5e pod.
    ok = ok and cfg.param_count() > 5e10
    return "tp2d" if ok else "fsdp"


def _rules():
    return [
        # embeddings: vocab-parallel over the TP axis (Megatron): logits come
        # out vocab-sharded and the loss reduces them without a gather
        (r"embed/table$", ("model", None)),
        (r"unembed/w$", (None, "model")),
        (r"unembed/b$", ("model",)),
        # MoE: experts over model (EP), d_model over fsdp
        (r"moe/router$", (None, None)),
        (r"moe/w_(up|gate)$", ("model", FSDP, None)),
        (r"moe/w_down$", ("model", None, FSDP)),
        # rwkv channel-mix down projection (ff, d)
        (r"cmix/wv/w$", ("model", FSDP)),
        # row-parallel (output) projections
        (r"(wo|w_down|w_out)/w$", ("model", FSDP)),
        # column-parallel (input) projections
        (r"(wq|wk|wv|wg|w_up|w_gate|wr|w_x|w_gate_branch|w_input_gate|"
         r"w_rec_gate|wk)/w$", (FSDP, "model")),
        # q/k norm scales follow their column-parallel projections (the
        # norm's one psum over the full width: models/blocks.py _qk_norm)
        (r"(q_norm|k_norm)/scale$", ("model",)),
        (r"w_lora_a$", (FSDP, None)),
        (r"w_lora_b$", (None, FSDP)),
        # everything small: replicate
        (r".*", ()),
    ]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    # PositArray leaves flatten to a trailing GetAttrKey('bits') child; the
    # rules name the parameter, so that key is transparent to the regexes
    # (a genuine dict entry named "bits" is a DictKey and is kept)
    if (path and isinstance(path[-1], jax.tree_util.GetAttrKey)
            and path[-1].name == "bits"):
        parts.pop()
    return "/".join(parts)


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _fit(spec_trailing, shape, mesh):
    """Pad with leading None to ndim; drop axes whose size doesn't divide."""
    nd = len(shape)
    spec = (None,) * (nd - len(spec_trailing)) + tuple(spec_trailing)
    spec = spec[:nd] if len(spec) > nd else spec
    fixed = []
    for dim, ax in zip(shape, spec):
        if ax is None or dim % _axis_size(mesh, ax) != 0:
            fixed.append(None)
        else:
            fixed.append(ax)
    return P(*fixed)


def param_pspecs(params, mesh, multi_pod: bool, strategy: str = "tp2d"):
    """PartitionSpec pytree for a model param tree (also fits opt moments)."""
    if strategy == "fsdp":
        return _fsdp_param_pspecs(params, mesh)
    fsdp = ("pod", "data") if multi_pod else "data"
    rules = [(re.compile(pat), spec) for pat, spec in _rules()]

    def assign(path, leaf):
        ps = _path_str(path)
        for pat, trailing in rules:
            if pat.search(ps):
                tr = tuple(fsdp if a == FSDP else a for a in trailing)
                return _fit(tr, leaf.shape, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def _fsdp_param_pspecs(params, mesh):
    """Pure FSDP: shard one dim of every matrix over the flat (data, model)
    axes (replicated across 'pod'; cross-pod sync is plain DP, where the
    posit-compressed collective applies).  Prefers the reduction (-2) dim,
    falls back to any dim that divides."""
    dm = ("data", "model")
    n = _axis_size(mesh, dm)

    def assign(path, leaf):
        if leaf.ndim < 2:
            return P()
        order = [leaf.ndim - 2, leaf.ndim - 1] + list(range(leaf.ndim - 2))
        for d in order:
            if leaf.shape[d] >= n and leaf.shape[d] % n == 0:
                spec = [None] * leaf.ndim
                spec[d] = dm
                return P(*spec)
        # half-flat fallback: data axis only
        for d in order:
            nd = _axis_size(mesh, "data")
            if leaf.shape[d] >= nd and leaf.shape[d] % nd == 0:
                spec = [None] * leaf.ndim
                spec[d] = "data"
                return P(*spec)
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def serving_param_pspecs(params, mesh):
    """Megatron inference-TP specs for the sharded paged serving step.

    Column/row-parallel weights over 'model' (one psum per block, applied
    by the blocks under distributed.collectives.tensor_parallel), vocab-
    parallel embed/unembed when the vocab divides.  Replicated over 'data':
    serving holds no optimizer state, so there is nothing to FSDP — every
    data-parallel replica reads the same (posit-narrow) weights.  Reuses
    the training rules with the FSDP placeholder dropped to replication,
    plus column-parallel qkv/gate bias sharding (training replicates
    biases; under TP a column-parallel output needs its bias shard-local).
    """
    # NOTE on MoE expert parallelism: the base moe/ rules already give the
    # serving layout once FSDP drops to replication — experts over the
    # model axis for w_up/w_gate/w_down (the shard-local grouped GEMM +
    # one block_psum combine in models/moe.py) and a replicated router, so
    # every shard routes identically.  No extra entries needed.
    extra = [(r"(wq|wk|wv|wg|w_up|w_gate|wr)/b$", ("model",))]
    rules = [(re.compile(pat), spec) for pat, spec in extra + _rules()]

    def assign(path, leaf):
        ps = _path_str(path)
        for pat, trailing in rules:
            if pat.search(ps):
                tr = tuple(None if a == FSDP else a for a in trailing)
                return _fit(tr, leaf.shape, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def train_param_pspecs(params, mesh):
    """Megatron TP specs for the shard_map training step.

    Serving's column/row-parallel layout minus vocab parallelism: embed
    and unembed tables stay replicated so the loss (softmax over the full
    vocab) and the embedding-table gradient need no vocab-shard psums —
    the unembed matmul is then replicated compute, which is exactly why
    the training step applies the f-operator (collectives.block_grad_sync)
    at TP block entries only and never at the final norm.  FSDP dims drop
    to replication ('data' carries pure DP with the posit-compressed
    gradient sync instead); column-parallel biases shard like serving.
    """
    extra = [
        (r"embed/table$", (None, None)),
        (r"unembed/w$", (None, None)),
        (r"unembed/b$", (None,)),
        (r"(wq|wk|wv|wg|w_up|w_gate|wr)/b$", ("model",)),
    ]
    rules = [(re.compile(pat), spec) for pat, spec in extra + _rules()]

    def assign(path, leaf):
        ps = _path_str(path)
        for pat, trailing in rules:
            if pat.search(ps):
                tr = tuple(None if a == FSDP else a for a in trailing)
                return _fit(tr, leaf.shape, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def paged_pool_pspecs(pages, mesh):
    """Serving pool specs, per backend (serving/backends.py):

    Paged KV leaves [.., num_pages, n_kv, page, D]: the page dim shards over
    'data' (each DP shard owns a private sub-pool with its own garbage page
    — the host scheduler in serving.engine allocates shard-locally), kv
    heads over 'model' when they divide (the TP attention heads live next
    to their pages).

    State-pool leaves (wkv/tshift/cshift/h/conv): the slot dim shards over
    'data' (slots are striped across DP shards exactly like the page-table
    rows), and the wkv head dim over 'model' when it divides (head-sharded
    state; the engine currently rejects TP for recurrent patterns, so this
    is layout support, not a dispatch path).

    Leaves may carry a leading stacked-reps dim for scanned layer groups.
    """
    from repro.core.array import PositArray
    from repro.serving.backends import _STATE_BASE_NDIM

    def kv_assign(leaf):
        spec = [None] * leaf.ndim
        spec[leaf.ndim - 4] = "data"
        if leaf.shape[leaf.ndim - 3] % _axis_size(mesh, "model") == 0:
            spec[leaf.ndim - 3] = "model"
        return P(*spec)

    def state_assign(name, leaf):
        slot = leaf.ndim - _STATE_BASE_NDIM[name]     # 0 unstacked, 1 stacked
        spec = [None] * leaf.ndim
        spec[slot] = "data"
        if (name == "wkv"
                and leaf.shape[slot + 1] % _axis_size(mesh, "model") == 0):
            spec[slot + 1] = "model"
        return P(*spec)

    def layer(p):
        if "k_pages" in p:
            # stop at PositArray (one spec covers its bits leaf): the spec
            # tree stays a plain-P prefix tree usable by shard_map and
            # device_put alike
            return jax.tree_util.tree_map(
                kv_assign, p, is_leaf=lambda x: isinstance(x, PositArray))
        return {k: state_assign(k, v) for k, v in p.items()}

    return {"scanned": tuple(layer(p) for p in pages["scanned"]),
            "rem": tuple(layer(p) for p in pages["rem"])}


def opt_state_pspecs(opt_state, param_specs, mesh):
    """Moments mirror parameter sharding; scalars (step, the NaR-guard
    skip counter) are replicated.  Keys mirror the opt_state actually
    passed so pre-nar_skips checkpoints still shard cleanly."""
    specs = {
        "step": P(),
        "m": param_specs,
        "v": param_specs,
    }
    for k in opt_state:
        if k not in specs:
            specs[k] = P()
    return specs


def dp_axes(mesh, multi_pod: bool, strategy: str):
    """Candidate batch axes, widest first."""
    base = ("pod", "data") if multi_pod else ("data",)
    if strategy == "fsdp":
        return [base + ("model",), ("data", "model"), base, ("data",)]
    return [base, ("data",)]


def batch_pspecs(batch, mesh, multi_pod: bool, shard_seq: bool = False,
                 strategy: str = "tp2d"):
    """Input batch: batch dim over the widest dividing DP axes; optionally
    sequence over data (sequence parallelism, e.g. long_500k)."""
    cands = dp_axes(mesh, multi_pod, strategy)

    def assign(leaf):
        if leaf.ndim == 0:
            return P()
        bdim = leaf.shape[0]
        for dp in cands:
            if bdim % _axis_size(mesh, tuple(dp)) == 0:
                return _fit((tuple(dp),) + (None,) * (leaf.ndim - 1),
                            leaf.shape, mesh)
        if shard_seq and leaf.ndim >= 2:
            return _fit((None, "data") + (None,) * (leaf.ndim - 2),
                        leaf.shape, mesh)
        return P()

    return jax.tree_util.tree_map(assign, batch)


def cache_pspecs(caches, mesh, multi_pod: bool, strategy: str = "tp2d"):
    """KV caches: batch over DP when divisible, else sequence over data;
    model axis on kv heads if they divide, else on head_dim."""
    dp = ("pod", "data") if multi_pod else ("data",)

    def assign(path, leaf):
        ps = _path_str(path)
        if leaf.ndim == 0:
            return P()
        shape = leaf.shape
        # stacked scan dim possible at axis 0: detect KV buffers by name
        if ps.endswith("/k") or ps.endswith("/v"):
            nd = leaf.ndim
            spec = [None] * nd
            b_ax, h_ax, s_ax, d_ax = nd - 4, nd - 3, nd - 2, nd - 1
            if shape[b_ax] % _axis_size(mesh, tuple(dp)) == 0:
                spec[b_ax] = tuple(dp)
            elif shape[s_ax] % mesh.shape["data"] == 0:
                spec[s_ax] = "data"
            # model axis: kv heads if they divide; else the sequence dim
            # (flash-decoding layout — softmax stats psum instead of KV
            # gathers); head_dim as the last resort
            if shape[h_ax] % mesh.shape["model"] == 0:
                spec[h_ax] = "model"
            elif spec[s_ax] is None and shape[s_ax] % mesh.shape["model"] == 0:
                spec[s_ax] = "model"
            elif shape[d_ax] % mesh.shape["model"] == 0:
                spec[d_ax] = "model"
            return P(*spec)
        # recurrent states (rwkv/rglru) and lengths: shard batch when it
        # divides, else replicate (states are small)
        for b_ax in (1, 0):
            if (leaf.ndim > b_ax
                    and shape[b_ax] % _axis_size(mesh, tuple(dp)) == 0
                    and shape[b_ax] >= _axis_size(mesh, tuple(dp))):
                spec = [None] * leaf.ndim
                spec[b_ax] = tuple(dp)
                return P(*spec)
        return P()

    return jax.tree_util.tree_map_with_path(assign, caches)


def to_shardings(pspecs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# activation sharding constraints (set by launch-layer code; no-op without)
# --------------------------------------------------------------------------
import contextlib
import threading

_ACT = threading.local()


@contextlib.contextmanager
def activation_sharding(mesh, multi_pod: bool, strategy: str = "tp2d"):
    """While active, shard_activation() pins key activations to the mesh.
    Trainer/dryrun wrap tracing in this; single-device tests skip it."""
    prev = getattr(_ACT, "ctx", None)
    _ACT.ctx = (mesh, multi_pod, strategy)
    try:
        yield
    finally:
        _ACT.ctx = prev


def shard_activation(x, kind: str):
    """kind: 'tokens' | 'act' | 'logits'.  Identity when no context."""
    ctx = getattr(_ACT, "ctx", None)
    if ctx is None or x.ndim == 0:
        return x
    mesh, multi_pod, strategy = ctx
    spec = None
    for dp in dp_axes(mesh, multi_pod, strategy):
        if x.shape[0] % _axis_size(mesh, tuple(dp)) == 0:
            if kind == "logits" and strategy == "tp2d":
                trailing = ((tuple(dp),) + (None,) * (x.ndim - 2)
                            + ("model",))
            elif kind == "act" and strategy == "tp2d" and x.ndim >= 3:
                # Megatron sequence parallelism: the inter-block residual
                # stream shards its sequence dim over the TP axis — scan-
                # carry residuals shrink 16x and block-boundary psums become
                # reduce-scatter/all-gather pairs (§Perf iteration A)
                trailing = ((tuple(dp), "model") + (None,) * (x.ndim - 2))
            elif kind == "kv_seq":
                # flash-decoding layout: KV [B,H,S,D] sharded on sequence
                trailing = (tuple(dp), None, "model", None)[:x.ndim]
            elif kind == "batch_only":
                # small per-step tensors (decode q): batch-sharded only,
                # replicated over the TP axis so the S-sharded KV einsum
                # partitions on S without gathers
                trailing = (tuple(dp),) + (None,) * (x.ndim - 1)
            elif kind == "block_in" and strategy == "tp2d":
                # Megatron-SP block entry: gather the sequence (replicate on
                # the TP axis) so weight gradients contract an unsharded
                # token dim and materialize at TP-sharded shape instead of
                # full (d, ff) partials (§Perf iteration A4)
                trailing = (tuple(dp),) + (None,) * (x.ndim - 1)
            else:
                trailing = (tuple(dp),) + (None,) * (x.ndim - 1)
            spec = _fit(trailing, x.shape, mesh)
            break
    if spec is None or spec == P(*(None,) * x.ndim):
        # batch unshardable (e.g. B=1 long-context): shard sequence on data
        if x.ndim >= 2 and x.shape[1] % _axis_size(mesh, "data") == 0:
            tr = (None, "data") + (None,) * (x.ndim - 2)
            if (kind == "logits" and strategy == "tp2d"
                    and x.shape[-1] % _axis_size(mesh, "model") == 0):
                tr = tr[:-1] + ("model",)
            spec = _fit(tr, x.shape, mesh)
        else:
            return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
