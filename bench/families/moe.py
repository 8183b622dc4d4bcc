"""The sparse-expert block of OLMoE (allenai/OLMoE-1B-7B-0924): attention
with RMSNorm over the full q and k projections, then a mixture of SwiGLU
experts whose router's top-k softmax probabilities are used as they are
(not renormalized), every layer; untied embeddings.

Its plain reference, written from the configuration file alone like the
dense one (bench/reference.py, whose helpers it borrows):

  x = posit(embed)[tokens]
  per layer:  h = rms(x) * ln1
              q = rms(h @ posit(Wq)) * q_norm;  k = rms(h @ posit(Wk)) * k_norm
              v = h @ posit(Wv);  q, k rotated;  k, v rounded to the KV posit
              x += causal_softmax(q k^T / sqrt(hd)) v @ posit(Wo)
              h = rms(x) * ln2;  p = softmax(h @ router)
              x += sum over the routed experts e of
                   p_e * (silu(h @ posit(Wg_e)) * (h @ posit(Wu_e))) @ posit(Wd_e)
  logits = (rms(x) * ln_f) @ posit(unembed)

in f32 jax.numpy under "highest" precision (or, for the control,
bfloat16 operands), with no kernels, cache or sorting: every expert is
computed for every token, in blocks of experts, and weighted by the combine
weights, which are zero off the routed ones.

Which experts are routed is taken from the program: `collect` reads the
sampled sequences' routing from the engine's record, and at each (token,
layer) the reference uses the program's top-k experts with its own softmax
probabilities.  A near-tie at the k-th expert, where a rounding of ~1e-6
picks another expert and every later position diverges, then no longer
moves the compared logits; instead each (token, layer) where the two top-k
sets differ is measured: `route_gap` is the largest distance, in the
reference's router logits, between an expert on which the sets differ and
the reference's own k-th logit (a tie tolerance), and `route_swaps`
counts such differences.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench import work
from bench.posit_ref import checked_round

# the dense family's kernels, and the grouped expert GEMM (its
# `pallas_call` is named `posit_grouped_gemm`)
KERNELS = {
    "gemm": ("posit_gemm.",),
    "attn": ("paged_flash_decode.", "paged_flash_prefill."),
    "moe": ("posit_grouped_gemm.",),
}

EXPERT_BLOCK = 8          # experts computed together by the reference
PAD = 256                 # sequences are padded to a multiple of this


def _moe(m: dict) -> tuple:
    e = m["moe"]
    return e["n_experts"], e["top_k"], e["norm_topk"]


def stated(m: dict) -> dict:
    return {"act": m["act"], "tie_embeddings": m["tie_embeddings"],
            "rope_theta": m["rope_theta"], "hd": m["head_dim"],
            "norm": "rmsnorm", "block_pattern": ("attn",),
            "qkv_bias": False, "embed_scale": False,
            "qk_norm": m["qk_norm"], "moe": _moe(m)}


def got(cfg) -> dict:
    """What the program's `cfg` says; a program without q/k norm or the
    top-k choice reads None there, so the check names what it lacks."""
    moe = cfg.moe
    return {"act": cfg.act, "tie_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "hd": cfg.hd, "norm": cfg.norm,
            "block_pattern": cfg.block_pattern, "qkv_bias": cfg.qkv_bias,
            "embed_scale": cfg.embed_scale,
            "qk_norm": getattr(cfg, "qk_norm", None),
            "moe": None if moe is None else (
                moe.n_experts, moe.top_k, getattr(moe, "norm_topk", None))}


def leaf_specs(m: dict) -> dict:
    """{path: (per-layer shape, layered, kind)}, as bench/weights.py's, for
    the program's parameter tree of this block."""
    d, hd = m["d_model"], m["head_dim"]
    H, KV, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    E = m["moe"]["n_experts"]
    s = {path: spec for path, spec in W.leaf_specs(m).items()
         if "/mlp/" not in path}
    s.update({
        "unembed/w": ((d, m["vocab"]), False, "matrix"),
        "scanned/0/attn/q_norm/scale": ((H * hd,), True, "scale"),
        "scanned/0/attn/k_norm/scale": ((KV * hd,), True, "scale"),
        "scanned/0/moe/router": ((d, E), True, "matrix"),
        "scanned/0/moe/w_up": ((E, d, ff), True, "matrix"),
        "scanned/0/moe/w_gate": ((E, d, ff), True, "matrix"),
        "scanned/0/moe/w_down": ((E, ff, d), True, "matrix")})
    return s


def make_flat(key, m: dict) -> dict:
    """{path: f32 array} for the whole model (stacked leaves [L, ...])."""
    out = {}
    for path, spec in leaf_specs(m).items():
        if spec[1]:
            out[path] = jnp.stack([W.make_leaf(key, path, spec, layer)
                                   for layer in range(m["n_layers"])])
        else:
            out[path] = W.make_leaf(key, path, spec)
    return out


nest = W.nest


def model_key(m: dict):
    """The hashable view of the model block the jitted functions key on."""
    return R.model_key(m) + (("moe", tuple(sorted(m["moe"].items()))),
                             ("tie_embeddings", m["tie_embeddings"]))


def _unkey(mkey) -> dict:
    m = dict(mkey)
    m["moe"] = dict(m["moe"])
    return m


# ---------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _served(key, layer, mkey, layered: bool, pcfg):
    """One layer's leaves (or, with layered False, the unlayered ones),
    drawn and quantized by the program's PTQ on the device."""
    from bench.program import quantize_for_serving
    m = _unkey(mkey)
    flat = {}
    for path, spec in leaf_specs(m).items():
        if spec[1] == layered:
            flat[path.split("/", 2)[2] if layered else path] = W.make_leaf(
                key, path, spec, layer if layered else None)
    return quantize_for_serving(W.nest(flat), pcfg)


def serving_params(seed: int, m: dict, cfg):
    """Random weights from the seed, quantized by the program's PTQ, made
    on the device layer by layer: the f32 tree is never whole (1.88 B
    parameters at the cell's size), only one layer's at a time."""
    key, mk, pcfg = W.seed_key(seed), model_key(m), cfg.policy.weights
    layers = []
    for layer in range(m["n_layers"]):
        one = _served(key, jnp.int32(layer), mk, True, pcfg)
        one.pop("rem")
        layers.append(one)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    del layers
    tree = _served(key, jnp.int32(0), mk, False, pcfg)
    tree["scanned"] = (stacked,)
    return tree


# -------------------------------------------------------------- reference
def _round_weight(m, path, x):
    """The matrices the program stores in posit are rounded to it; the norm
    scales and the router stay f32, as the program's PTQ keeps them."""
    if path.endswith("scale") or path.endswith("router"):
        return x, jnp.int32(0)
    n, es = m["weight_posit"]
    return checked_round(x, n, es)


def _mm(precision: str):
    """bench/reference.py's matrix product; the control's bfloat16 operands
    are rounded first and multiplied in f32 (exact products, f32 sums: the
    same numbers, and a form every backend runs)."""
    if precision != "bf16":
        return R._mm(precision)

    def mm(spec, a, b):
        bf = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
        return jnp.einsum(spec, bf(a), bf(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def _experts(m, mm, lw, h, w):
    """sum_e w[:, e] * expert_e(h), every expert computed, EXPERT_BLOCK at
    a time.  h [S, d], w [S, E]."""
    E = m["moe"]["n_experts"]
    nb = E // EXPERT_BLOCK

    def blocks(name):
        x = lw[name]
        return x.reshape((nb, EXPERT_BLOCK) + x.shape[1:])

    def one(acc, xs):
        wg, wu, wd, wb = xs
        g = mm("sd,edf->sef", h, wg)
        u = mm("sd,edf->sef", h, wu)
        y = mm("sef,efd->sed", jax.nn.silu(g) * u, wd)
        return acc + jnp.einsum("sed,se->sd", y, wb,
                                precision=jax.lax.Precision.HIGHEST), None

    wb = w.reshape(w.shape[0], nb, EXPERT_BLOCK).transpose(1, 0, 2)
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (blocks("moe/w_gate"), blocks("moe/w_up"),
                           blocks("moe/w_down"), wb))
    return out


def _block(m, precision, lw, x, r):
    """One layer over one sequence x [S, d]; r [S, k] the program's expert
    ids (-1: none recorded, the reference's own are used).  Returns the
    new x, the posit misses, and per position the route gap, the swaps,
    whether the program's routing was compared there, and the reference's
    own top-k ids."""
    mm = _mm(precision)
    kvn, kves = m["kv_posit"]
    eps = m["rms_norm_eps"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    E, k, norm_topk = _moe(m)
    S = x.shape[0]
    h = R._rms(x, lw["ln1/scale"], eps)
    q = R._rms(mm("sd,do->so", h, lw["attn/wq/w"]), lw["attn/q_norm/scale"],
               eps).reshape(S, H, hd)
    kk = R._rms(mm("sd,do->so", h, lw["attn/wk/w"]), lw["attn/k_norm/scale"],
                eps).reshape(S, KV, hd)
    v = mm("sd,do->so", h, lw["attn/wv/w"]).reshape(S, KV, hd)
    q, kk = R._rope(q, m["rope_theta"]), R._rope(kk, m["rope_theta"])
    kk, bad_k = checked_round(kk, kvn, kves)
    v, bad_v = checked_round(v, kvn, kves)
    G = H // KV
    s = mm("qkgd,tkd->kgqt", q.reshape(S, KV, G, hd), kk) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(S, H * hd)
    x = x + mm("so,od->sd", o, lw["attn/wo/w"])

    h = R._rms(x, lw["ln2/scale"], eps)
    logits = mm("sd,de->se", h, lw["moe/router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top, own = jax.lax.top_k(logits, k)
    own_set = jax.nn.one_hot(own, E, dtype=jnp.int32).sum(-2) > 0
    given = r[:, 0] >= 0
    prog_set = jax.nn.one_hot(jnp.where(r >= 0, r, 0), E,
                              dtype=jnp.int32).sum(-2) > 0
    used = jnp.where(given[:, None], prog_set, own_set)
    w = jnp.where(used, probs, 0.0)
    if norm_topk:
        w = w / w.sum(-1, keepdims=True)
    x = x + _experts(m, mm, lw, h, w)
    differ = (prog_set != own_set) & given[:, None]
    gap = jnp.where(differ, jnp.abs(logits - top[:, -1:]), 0.0).max(-1)
    swaps = (prog_set & ~own_set & given[:, None]).sum(-1)
    return x, bad_k + bad_v, gap, swaps, given, own


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(mkey, precision, key, layer, X, Rt):
    m = _unkey(mkey)
    lw, bad = {}, jnp.int32(0)
    for path, spec in leaf_specs(m).items():
        if spec[1]:
            short = path.split("/", 2)[2]
            lw[short], b = _round_weight(m, short, W.make_leaf(
                key, path, spec, layer))
            bad = bad + b
    out, b, gap, swaps, given, own = jax.lax.map(
        lambda a: _block(m, precision, lw, *a), (X, Rt))
    return out, bad + b.sum(), gap, swaps, given, own


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(mkey, key, tokens):
    m = _unkey(mkey)
    path = "embed/table"
    table, bad = _round_weight(m, path, W.make_leaf(
        key, path, leaf_specs(m)[path]))
    return table[tokens], bad


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(mkey, precision, key, X, pick, valid):
    """Per position: (reference's best logit - logit of token `pick`,
    argmax); valid marks the positions compared."""
    m = _unkey(mkey)
    mm = _mm(precision)
    specs = leaf_specs(m)
    table, bad = _round_weight(m, "unembed/w", W.make_leaf(
        key, "unembed/w", specs["unembed/w"]))
    ln_f = W.make_leaf(key, "ln_f/scale", specs["ln_f/scale"])

    def one(args):
        x, tok, ok = args
        logits = mm("sd,dv->sv", R._rms(x, ln_f, m["rms_norm_eps"]), table)
        best = logits.max(-1)
        got_ = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
        return jnp.where(ok, best - got_, 0.0), jnp.argmax(logits, -1)

    gaps, top = jax.lax.map(one, (X, pick, valid))
    return gaps, top, bad


def _forward(m, seed, seqs, first, length, precision, picks, routes):
    """The reference over the padded sequences: (gaps, argmaxes, posit
    misses, the routing comparison {route_gap, route_swaps, route_pairs,
    route_misses}, and per sequence the reference's own expert ids
    [positions, layers, k], as `collect` gives the program's)."""
    if precision not in R.PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {R.PRECISIONS}")
    mk = model_key(m)
    key = W.seed_key(seed)
    n, k = len(seqs), m["moe"]["top_k"]
    longest = max(len(s) for s in seqs)
    if longest > length:
        raise ValueError(f"sequence of {longest} tokens > {length}")
    Lp = min(length, -(-longest // PAD) * PAD)
    tokens = np.zeros((n, Lp), np.int32)
    valid = np.zeros((n, Lp), bool)
    pick = np.zeros((n, Lp), np.int32)
    # the program's expert ids at every position it computed (all but the
    # last token of a sequence); -1 elsewhere
    rt = np.full((m["n_layers"], n, Lp, k), -1, np.int32)
    misses = 0
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
        valid[i, first[i]:len(s) - 1] = True
        pick[i, :len(s) - 1] = s[1:]
        if picks is not None:
            pick[i, first[i]:len(s) - 1] = picks[i]
        if routes is not None:
            r = np.asarray(routes[i])[:len(s) - 1]
            rt[:, i, :len(r)] = r.transpose(1, 0, 2)
            misses += int((rt[:, i, :len(s) - 1, 0] < 0).sum())
    X, bad = _embed(mk, key, jnp.asarray(tokens))
    gaps_r, swaps, pairs, own = [], 0, 0, []
    for layer in range(m["n_layers"]):
        X, b, gap, sw, given, ids = _layer(mk, precision, key, layer, X,
                                           jnp.asarray(rt[layer]))
        bad = bad + b
        g, sw, given, ids = jax.device_get((gap, sw, given, ids))
        gaps_r.append(float(g.max()) if given.any() else 0.0)
        swaps += int(sw.sum())
        pairs += int(given.sum()) * k
        own.append(ids)
    own = np.stack(own, axis=2)                     # [n, pos, layers, k]
    gaps, top, b = _head(mk, precision, key, X, jnp.asarray(pick),
                         jnp.asarray(valid))
    gaps, top, bad = jax.device_get((gaps, top, bad + b))
    cut = [slice(first[i], len(s) - 1) for i, s in enumerate(seqs)]
    route = {"route_gap": max(gaps_r) if misses == 0 else float("nan"),
             "route_swaps": swaps, "route_pairs": pairs,
             "route_misses": misses}
    return ([gaps[i, c] for i, c in enumerate(cut)],
            [top[i, c] for i, c in enumerate(cut)], int(bad), route,
            [own[i, :len(s) - 1] for i, s in enumerate(seqs)])


def teacher_forced(m: dict, seed: int, seqs, first: list[int], length: int,
                   collected=None, precision: str = "highest", picks=None):
    """bench/reference.py `teacher_forced` for this block: (gaps, argmaxes,
    posit misses).  With `collected` (what `collect` took from the run)
    the reference routes every position the program computed as the
    program did, and the routing comparison is left in
    collected["route"] for `extra_checks`."""
    routes = None if collected is None else collected["routes"]
    gaps, tops, bad, route, _ = _forward(m, seed, seqs, first, length,
                                         precision, picks, routes)
    if collected is not None:
        collected["route"] = route
    return gaps, tops, bad


def control(m: dict, seed: int, seqs, first: list[int], length: int):
    """The control, the reference put in the program's place one precision
    lower: the bfloat16 reference's own first tokens and routing are read
    as the program's would be, against the "highest" reference.  Returns
    (gaps, posit misses, routing comparison)."""
    _, tops, bad_lo, _, own = _forward(m, seed, seqs, first, length, "bf16",
                                       None, None)
    gaps, _, bad, route, _ = _forward(m, seed, seqs, first, length,
                                      "highest", tops, own)
    return gaps, bad + bad_lo, route


def control_gaps(m: dict, seed: int, seqs, first: list[int], length: int):
    """bench/reference.py `control_gaps` for this block (the control's
    routing comparison is `control`'s)."""
    gaps, bad, _ = control(m, seed, seqs, first, length)
    return gaps, bad


# --------------------------------------------------------- from the run
def _expert_rows(E: int, ids, nums) -> np.ndarray:
    """[layers, E] rows routed to each expert by one step's record (ids
    [layers, seqs, width, k], nums [seqs] new tokens)."""
    L, _, width, k = ids.shape
    rows = ids[:, np.arange(width)[None, :] < np.asarray(nums)[:, None]]
    flat = rows.reshape(L, -1).astype(np.int64) + E * np.arange(L)[:, None]
    return np.bincount(flat.ravel(), minlength=L * E).reshape(L, E)


def collect(engine, sample):
    """From the engine's routing record: each sampled request's expert ids
    at every position it computed ([positions, layers, k], in the order of
    `sample`), and the rows each expert received in each recorded step."""
    E = engine.cfg.moe.n_experts
    return {"routes": [engine.routing(rid) for rid in sample],
            "expert_rows": [_expert_rows(E, ids, nums) for _, _, nums, ids
                            in engine.step_routes]}


def extra_checks(m, check, collected) -> dict:
    """route_gap (limit: the configuration's tie tolerance, in router
    logits); route_swaps, the top-k slots where the program's expert
    differs from the reference's, against the number of slots compared,
    which it can never pass: reported, not limited."""
    r = collected["route"]
    return {"route_gap": (r["route_gap"], check["route_gap"], "max"),
            "route_swaps": (r["route_swaps"], r["route_pairs"], "max")}


def step_work(m, rows, records) -> list[dict]:
    """The dense family's classes (bench/work.py) less the dense MLP, whose
    place the experts take: `gemm` (the q, k, v, o projections and the
    unembedding) and `attn`; and `moe`: per layer, every expert that rows
    were routed to reads its three posit matrices once and computes its
    rows, f32 activations in and out.  `model` is the forward's operations
    over the active parameters (the routed experts and the router
    included)."""
    d, ff, L = m["d_model"], m["d_ff"], m["n_layers"]
    E, k, _ = _moe(m)
    wb = m["weight_posit"][0] // 8
    ran = [r for r in rows if r]
    loads = records["expert_rows"][len(records["expert_rows"]) - len(ran):]
    if len(loads) != len(ran):
        raise SystemExit("bench: the routing record holds fewer steps than "
                         "the window ran")
    loads = iter(loads)

    def swiglu(n):
        out = work.Work()
        for kk, nn in [(d, ff), (d, ff), (ff, d)]:
            out += work.pw_gemm(n, kk, nn, wb)
        return out

    out = []
    for slots in rows:
        w = work.step_work(m, slots)
        w["moe"] = work.Work()
        out.append(w)
        if not slots:
            continue
        load = next(loads)
        T = sum(n for n, _ in slots)
        if not (load.sum(axis=1) == T * k).all():
            raise SystemExit("bench: the routing record and the window's "
                             "rows disagree on a step's tokens")
        mlp = swiglu(T)
        w["gemm"] = work.Work(w["gemm"].ops - L * mlp.ops,
                              w["gemm"].bytes - L * mlp.bytes)
        for r in load[load > 0].tolist():
            w["moe"] += swiglu(r)
        router = 2.0 * L * T * d * E
        w["model"] = work.Work(w["gemm"].ops + w["attn"].ops + w["moe"].ops
                               + router, 0)
    return out
