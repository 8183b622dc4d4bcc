"""Grouped posit MoE: sort-based routing + the grouped GEMM kernel vs the
GShard one-hot oracle.

Covers: kernel-vs-reference parity at ragged/empty group sizes (float, p8,
p16), the zero-rows-outside-groups contract, grouped moe_block vs oracle
parity on the olmoe and qwen3 smoke shapes, the forced-drop combine-weight
renormalization (pinned against an independent numpy oracle), custom_vjp
gradients (kernel forward, segment-sum reference backward), the
no-dense-decode guarantee across a full engine drain (DENSE_MOE_FALLBACKS),
serving's batch-independence (no capacity coupling between requests), and
expert-parallel TP serving on a forced multi-device host.

Everything kernel-shaped runs in interpret mode, so regressions fail in
tier-1 before the nightly TPU lane sees them.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core.convert import f32_to_posit
from repro.core.types import P8_2, P16_2
from repro.kernels import ops as kops
from repro.kernels.grouped_gemm import posit_grouped_gemm
from repro.kernels.ref import grouped_matmul_ref
from repro.models import moe as MOE
from repro.models.transformer import ModelConfig, init_params
from repro.quant.policy import NONE, PositPolicy, quantize_tree

# multi-k-tile kernels split the contraction into per-tile partial sums, so
# parity with the single-dot reference is f32-accumulation-order loose
TOL = dict(rtol=2e-4, atol=2e-5)


def _pallas_interpret_env(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("REPRO_FORCE_GATHER", raising=False)


# --------------------------------------------------------------------------
# the kernel itself: ragged groups, empty groups, rows outside every group
# --------------------------------------------------------------------------
@pytest.mark.parametrize("pcfg", [None, P16_2, P8_2],
                         ids=["float", "p16", "p8"])
@pytest.mark.parametrize("sizes,tail", [
    ([0, 7, 0, 13, 4], 0),          # empty groups between ragged ones
    ([5, 0, 0, 0, 19], 0),          # leading singleton + empty run
    ([10, 3, 9, 6, 2], 3),          # offsets[-1] < S: unowned tail rows
    ([0, 0, 0], 16),                # every group empty
], ids=["ragged", "sparse", "tail", "all-empty"])
def test_grouped_gemm_matches_ref(pcfg, sizes, tail):
    rng = np.random.default_rng(0)
    E = len(sizes)
    S = int(sum(sizes)) + tail
    K, N = 32, 48
    off = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    x = jnp.asarray(rng.normal(size=(S, K)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    w = f32_to_posit(wd, pcfg) if pcfg is not None else wd
    got = posit_grouped_gemm(x, w, off, cfg_b=pcfg, bm=8, bn=128, bk=16,
                             interpret=True)
    ref = grouped_matmul_ref(x, w, off, cfg_b=pcfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)
    if tail:
        # rows past offsets[-1] belong to no group: exact zeros, not the
        # unwritten-buffer garbage of the untouched output tiles
        assert np.array_equal(np.asarray(got[-tail:]), np.zeros((tail, N)))


def test_grouped_gemm_tile_straddling_groups():
    """Group boundaries strictly inside an m-tile: the tile is visited once
    per group and the visits' row sets must compose, not clobber."""
    rng = np.random.default_rng(1)
    sizes = [3, 2, 3, 5, 3]                      # every boundary mid-tile
    E, S, K, N = len(sizes), sum(sizes), 16, 24
    off = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    x = jnp.asarray(rng.normal(size=(S, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    got = posit_grouped_gemm(x, w, off, cfg_b=None, bm=8, bn=128, bk=16,
                             interpret=True)
    ref = grouped_matmul_ref(x, w, off, cfg_b=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("transpose_b", [False, True], ids=["fwd", "dx"])
@pytest.mark.parametrize("precision", ["highest", "default"])
def test_grouped_gemm_dots_take_the_matmul_precision(precision, transpose_b):
    """The kernel's dots carry no precision of their own: traced under
    jax.default_matmul_precision they take the context's, which Mosaic
    lowers to an f32 contraction for "highest" (the precision a posit16
    serving step states) and to its bf16 default otherwise."""
    import re
    E, K, N = 4, 256, 128
    x = jax.ShapeDtypeStruct((64, N if transpose_b else K), jnp.float32)
    w = jax.ShapeDtypeStruct((E, K, N), jnp.int16)
    off = jax.ShapeDtypeStruct((E + 1,), jnp.int32)
    with jax.default_matmul_precision(precision):
        text = str(jax.make_jaxpr(
            lambda a, b, c: posit_grouped_gemm(
                a, b, c, cfg_b=P16_2, transpose_b=transpose_b,
                interpret=True))(x, w, off))
    got = re.findall(r"precision=\(Precision\.(\w+), Precision\.(\w+)\)",
                     text)
    assert got and set(got) == {(precision.upper(),) * 2}, got


def test_grouped_matmul_dispatch_requires_cfg_for_raw_ints():
    x = jnp.zeros((4, 8), jnp.float32)
    w = jnp.zeros((2, 8, 8), jnp.int16)
    off = jnp.asarray([0, 2, 4], jnp.int32)
    with pytest.raises(TypeError, match="format"):
        kops.grouped_matmul(x, w, off)


# --------------------------------------------------------------------------
# moe_block: grouped path vs the GShard one-hot oracle
# --------------------------------------------------------------------------
def _smoke_moe_shapes():
    out = []
    for arch in ("olmoe-1b-7b", "qwen3-moe-235b-a22b"):
        c = configs.get_smoke(arch)
        out.append((arch, c.d_model, c.d_ff, c.moe.n_experts, c.moe.top_k,
                    c.act))
    return out


@pytest.mark.parametrize("pcfg", [None, P16_2, P8_2],
                         ids=["float", "p16", "p8"])
@pytest.mark.parametrize("arch,d,ff,E,k,act", _smoke_moe_shapes(),
                         ids=["olmoe", "qwen3"])
def test_moe_grouped_matches_oneshot_oracle(monkeypatch, arch, d, ff, E, k,
                                            act, pcfg):
    p = MOE.init_moe(jax.random.PRNGKey(0), d, ff, E, act)
    if pcfg is not None:
        p = quantize_tree(p, pcfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d))
    kw = dict(n_experts=E, top_k=k, act=act, policy=NONE,
              capacity_factor=2.0, group_size=8)
    ref, aux_ref = MOE.moe_block(x, p, **kw)

    _pallas_interpret_env(monkeypatch)
    # capacity is set (training-shaped call), which keeps the one-hot path
    # even on the Pallas backend — pin the grouped dispatch explicitly
    monkeypatch.setattr(MOE, "FORCE_GROUPED", True)
    before = dict(MOE.DENSE_MOE_FALLBACKS)
    got, aux = MOE.moe_block(x, p, **kw)
    assert dict(MOE.DENSE_MOE_FALLBACKS) == before, \
        "grouped path materialized full expert tensors"
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)


def test_moe_grouped_no_capacity_matches_oracle(monkeypatch):
    """capacity_factor=None (the serving setting): no pair ever drops and
    both paths agree."""
    d, ff, E, k = 32, 48, 8, 2
    p = MOE.init_moe(jax.random.PRNGKey(2), d, ff, E, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, d))
    kw = dict(n_experts=E, top_k=k, act="swiglu", policy=NONE,
              capacity_factor=None, group_size=16)
    ref, _ = MOE.moe_block(x, p, **kw)
    _pallas_interpret_env(monkeypatch)
    got, _ = MOE.moe_block(x, p, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


# --------------------------------------------------------------------------
# forced drops: combine weights renormalize over the *kept* experts
# --------------------------------------------------------------------------
def _numpy_moe_oracle(x, p, *, n_experts, top_k, act, cap, group_size,
                      norm_topk=True):
    """Independent numpy reimplementation of routing + dispatch with the
    kept-only renormalization — the pinned semantics both paths must hit.
    norm_topk False: the kept pairs weigh their raw probabilities."""
    assert act == "gelu"
    B, S, d = x.shape
    T = B * S
    xt = np.asarray(x, np.float64).reshape(T, d)
    logits = xt @ np.asarray(p["router"], np.float64)
    z = np.exp(logits - logits.max(-1, keepdims=True))
    probs = z / z.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    gate = np.take_along_axis(probs, order, axis=-1)
    # arrival-order capacity per dispatch group
    fill = {}
    keep = np.zeros_like(gate, bool)
    for t in range(T):
        g = t // group_size
        for j in range(top_k):
            e = int(order[t, j])
            c = fill.get((g, e), 0)
            if c < cap:
                keep[t, j] = True
            fill[(g, e)] = c + 1
    kept = gate * keep
    w = kept / np.maximum(kept.sum(-1, keepdims=True), 1e-9) if norm_topk \
        else kept
    wu = np.asarray(p["w_up"], np.float64)
    wd = np.asarray(p["w_down"], np.float64)

    def expert_out(rows, e):
        # borrow jax's own gelu for the activation (reimplementing erf
        # would test library plumbing, not the routing semantics)
        h = np.asarray(jax.nn.gelu(jnp.asarray(rows @ wu[e])), np.float64)
        return h @ wd[e]

    out = np.zeros((T, d))
    for t in range(T):
        for j in range(top_k):
            if keep[t, j]:
                out[t] += w[t, j] * expert_out(xt[t][None, :],
                                               int(order[t, j]))[0]
    return out.reshape(B, S, d), keep


@pytest.mark.parametrize("grouped", [False, True], ids=["oneshot", "grouped"])
def test_forced_drop_renormalizes_over_kept_experts(monkeypatch, grouped):
    """cap=1 forces overflow: a token whose sibling expert dropped must put
    its full weight on the kept expert (renormalized over kept), not keep
    the stale pre-drop mix."""
    E, k, d, ff, B, S = 4, 2, 16, 24, 1, 8
    p = MOE.init_moe(jax.random.PRNGKey(4), d, ff, E, "gelu")
    x = jax.random.normal(jax.random.PRNGKey(5), (B, S, d))
    # cap = max(1, int(cf * gs * k / E)) = 1 with cf=0.25, gs=8, k=2, E=4
    want, keep = _numpy_moe_oracle(x, p, n_experts=E, top_k=k, act="gelu",
                                   cap=1, group_size=8)
    n_kept = keep.sum(-1)
    assert (n_kept == 1).any(), "seed produced no partial drop; test vacuous"
    if grouped:
        _pallas_interpret_env(monkeypatch)
        monkeypatch.setattr(MOE, "FORCE_GROUPED", True)
    got, _ = MOE.moe_block(x, p, n_experts=E, top_k=k, act="gelu",
                           policy=NONE, capacity_factor=0.25, group_size=8)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("grouped", [False, True], ids=["oneshot", "grouped"])
def test_norm_topk_false_keeps_raw_probabilities(monkeypatch, grouped):
    """norm_topk=False (OLMoE): the combine weights are the kept top-k
    softmax probabilities as they are, dropped pairs 0, on both dispatch
    paths; with capacity drops forced as in the test above."""
    E, k, d, ff, B, S = 4, 2, 16, 24, 1, 8
    p = MOE.init_moe(jax.random.PRNGKey(4), d, ff, E, "gelu")
    x = jax.random.normal(jax.random.PRNGKey(5), (B, S, d))
    xt = x.reshape(1, S, d)
    probs, idx, _, _, keep, w = MOE._route(
        xt, p, n_experts=E, top_k=k, cap=1, policy=NONE, norm_topk=False)
    raw = jnp.take_along_axis(probs, idx, axis=-1)
    np.testing.assert_array_equal(np.asarray(w),
                                  np.asarray(jnp.where(keep, raw, 0.0)))
    assert not bool(keep.all()), "no pair dropped; test vacuous"
    want, _ = _numpy_moe_oracle(x, p, n_experts=E, top_k=k, act="gelu",
                                cap=1, group_size=8, norm_topk=False)
    if grouped:
        _pallas_interpret_env(monkeypatch)
        monkeypatch.setattr(MOE, "FORCE_GROUPED", True)
    got, _ = MOE.moe_block(x, p, n_experts=E, top_k=k, act="gelu",
                           policy=NONE, capacity_factor=0.25, group_size=8,
                           norm_topk=False)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-4)
    # and renormalizing is another answer
    renorm, _ = MOE.moe_block(x, p, n_experts=E, top_k=k, act="gelu",
                              policy=NONE, capacity_factor=0.25,
                              group_size=8)
    assert float(jnp.abs(renorm - got).max()) > 1e-3


# --------------------------------------------------------------------------
# custom_vjp: kernel forward, jnp segment-sum reference backward
# --------------------------------------------------------------------------
def test_grouped_matmul_grads_match_dense_reference(monkeypatch):
    rng = np.random.default_rng(6)
    sizes = [5, 0, 9, 2]
    E, S, K, N = len(sizes), sum(sizes), 16, 24
    off = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    x = jnp.asarray(rng.normal(size=(S, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    gid = np.repeat(np.arange(E), sizes)

    def dense_loss(x, w):
        out = jnp.einsum("sk,skn->sn", x, w[jnp.asarray(gid)])
        return (out * jnp.sin(out)).sum()

    def grouped_loss(x, w):
        out = kops.grouped_matmul(x, w, off)
        return (out * jnp.sin(out)).sum()

    ref = jax.grad(dense_loss, argnums=(0, 1))(x, w)
    _pallas_interpret_env(monkeypatch)
    got = jax.grad(grouped_loss, argnums=(0, 1))(x, w)
    for name, a, b in zip("xw", got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=f"d{name} diverged")


def test_moe_block_grads_grouped_matches_oracle(monkeypatch):
    """End-to-end moe_block gradients (routing + custom_vjp + scatter
    combine + STE posit weights) agree between the two dispatch paths."""
    E, k, d, ff = 8, 2, 32, 48
    p = MOE.init_moe(jax.random.PRNGKey(7), d, ff, E, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, d))
    pol = PositPolicy(weights=P16_2)

    def loss(p, x):
        out, aux = MOE.moe_block(x, p, n_experts=E, top_k=k, act="swiglu",
                                 policy=pol, capacity_factor=2.0,
                                 group_size=8)
        return (out * out).sum() + aux

    ref = jax.grad(loss)(p, x)
    _pallas_interpret_env(monkeypatch)
    monkeypatch.setattr(MOE, "FORCE_GROUPED", True)
    got = jax.grad(loss)(p, x)
    for kk in ref:
        np.testing.assert_allclose(np.asarray(got[kk]), np.asarray(ref[kk]),
                                   rtol=5e-4, atol=5e-5, err_msg=kk)


# --------------------------------------------------------------------------
# the acceptance row: engine drain with zero full-expert decodes
# --------------------------------------------------------------------------
def _olmoe_cfg(name):
    base = configs.get_smoke("olmoe-1b-7b")
    return ModelConfig(**{**base.__dict__, "name": name,
                          "policy": PositPolicy(kv_cache=P16_2)})


def test_engine_drain_grouped_no_dense_decode_and_bit_parity(monkeypatch):
    """A full continuous-batching drain of olmoe-1b-7b-smoke with PTQ posit
    weights through the interpret-mode kernels: the grouped path never
    materializes the [E, d, ff] expert tensors (DENSE_MOE_FALLBACKS stays
    untouched — the ISSUE-5 acceptance row) and greedy tokens match the jnp
    oracle engine."""
    from repro.serving import engine as E
    from repro.serving import paged_kv

    cfg = _olmoe_cfg("olmoe-drain-ref")
    params = quantize_tree(init_params(jax.random.PRNGKey(0), cfg), P16_2)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, cfg.vocab, int(rng.integers(3, 12))
                          ).astype(np.int32), 5) for _ in range(4)]

    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)
    ref = eng.run([(p.copy(), n) for p, n in reqs])
    # the oracle engine *did* decode the full expert tensors (it is the
    # counted dense path) — the counter moved
    assert MOE.DENSE_MOE_FALLBACKS["expert-decode"] > 0

    _pallas_interpret_env(monkeypatch)
    before = dict(MOE.DENSE_MOE_FALLBACKS)
    before_g = dict(paged_kv.GATHER_FALLBACKS)
    eng2 = E.PagedServingEngine(params, _olmoe_cfg("olmoe-drain-grouped"),
                                max_seqs=4, page_size=4, table_width=8,
                                prefill_chunk=8)
    res = eng2.run([(p.copy(), n) for p, n in reqs])
    assert dict(MOE.DENSE_MOE_FALLBACKS) == before, \
        "Pallas-path serving decoded the full expert tensors"
    assert dict(paged_kv.GATHER_FALLBACKS) == before_g
    for r in ref:
        assert np.array_equal(ref[r], res[r]), (r, ref[r], res[r])


def _routes_by_layer(params, cfg, seq):
    """The expert ids `_route` gives each position of `seq` in every layer
    ([positions, layers, top_k]): the forward pass written out layer by
    layer from the model's blocks, without caches, capacity or engine."""
    from repro.models import blocks as B
    from repro.models.transformer import _norm
    x = B.embed(jnp.asarray(seq)[None], params["embed"], cfg.policy)
    pos = jnp.arange(len(seq))[None]
    out = []
    for layer in range(cfg.n_layers):
        p = jax.tree_util.tree_map(lambda t: t[layer], params["scanned"][0])
        h, _ = B.attention_block(
            _norm(x, p["ln1"], cfg), p["attn"], n_heads=cfg.n_heads,
            n_kv=cfg.n_kv, head_dim=cfg.hd, positions=pos, policy=cfg.policy)
        x = x + h
        hn = _norm(x, p["ln2"], cfg)
        _, idx, *_ = MOE._route(hn, p["moe"], n_experts=cfg.moe.n_experts,
                                top_k=cfg.moe.top_k, cap=len(seq),
                                policy=cfg.policy,
                                norm_topk=cfg.moe.norm_topk)
        out.append(np.asarray(idx[0]))
        h, _ = MOE.moe_block(hn, p["moe"], n_experts=cfg.moe.n_experts,
                             top_k=cfg.moe.top_k, act=cfg.act,
                             policy=cfg.policy, capacity_factor=None,
                             group_size=len(seq),
                             norm_topk=cfg.moe.norm_topk)
        x = x + h
    return np.stack(out, axis=1)


def test_routing_record_matches_route():
    """The engine's routing record, read back per request, holds the expert
    ids `_route` gives every position the request computed (its prompt's
    prefill chunks and its decode steps), and its counters add up."""
    from repro.serving import engine as E

    cfg = _olmoe_cfg("olmoe-route-record")
    params = init_params(jax.random.PRNGKey(11), cfg)
    rng = np.random.default_rng(12)
    reqs = [(rng.integers(0, cfg.vocab, int(n)).astype(np.int32), 5)
            for n in (3, 11, 7, 14, 5)]
    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)
    res = eng.run([(p.copy(), n) for p, n in reqs])
    k, L = cfg.moe.top_k, cfg.n_layers
    computed = 0
    for rid, (prompt, _) in enumerate(reqs):
        seq = np.concatenate([prompt, res[rid][:-1]])
        rec = eng.routing(rid)
        assert rec.dtype == np.int8 and rec.shape == (len(seq), L, k)
        want = _routes_by_layer(params, cfg, seq)
        assert np.array_equal(np.sort(rec, -1), np.sort(want, -1)), rid
        computed += len(seq)
    st = eng.stats()
    assert st["moe_pairs"] == computed * L * k
    assert 0 < st["moe_experts_hit"] <= len(eng.step_routes) * L * \
        cfg.moe.n_experts
    assert 1 <= st["moe_rows_max"] <= 4 * 8
    eng.reset_stats()
    assert eng.stats()["moe_pairs"] == 0 and not eng.step_routes
    assert eng.routing(0).size == 0


def test_dense_step_returns_no_routing():
    """Only a MoE model's step returns routing: the dense step's outputs
    are unchanged."""
    from repro.serving import engine as E
    cfg = ModelConfig(name="dense-noroute", n_layers=1, d_model=16,
                      n_heads=2, n_kv=2, d_ff=32, vocab=32)
    eng = E.PagedServingEngine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                               max_seqs=2, page_size=4, table_width=4,
                               prefill_chunk=4)
    eng.run([(np.arange(5, dtype=np.int32), 3)])
    assert not eng.step_routes and eng.stats()["moe_pairs"] == 0


def test_serving_moe_output_independent_of_batch_composition():
    """Serving disables capacity dropping, so a request's tokens cannot
    depend on which other requests share its decode batch."""
    from repro.serving import engine as E

    cfg = _olmoe_cfg("olmoe-batchindep")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, 7).astype(np.int32)
    others = [(rng.integers(0, cfg.vocab, int(rng.integers(3, 10))
                            ).astype(np.int32), 5) for _ in range(3)]

    solo = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                                table_width=8, prefill_chunk=8)
    res_solo = solo.run([(prompt.copy(), 5)])
    crowd = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                                 table_width=8, prefill_chunk=8)
    res_crowd = crowd.run([(prompt.copy(), 5)] + others)
    assert np.array_equal(res_solo[0], res_crowd[0]), \
        "MoE serving output depends on batch composition"


# --------------------------------------------------------------------------
# expert-parallel TP serving (the lifted engine ValueError)
# --------------------------------------------------------------------------
def test_sharded_engine_validates_expert_divisibility():
    """The old blanket `TP over MoE blocks is not supported` is gone; the
    guard is now n_experts % ntp (each expert's d_ff stays whole on its
    shard, so d_ff is deliberately not checked for MoE archs)."""
    from repro.serving import engine as E

    class _FakeMesh:
        shape = {"data": 1, "model": 3}

    base = configs.get_smoke("olmoe-1b-7b")       # 8 experts
    # heads/kv divide the 3-wide model axis, experts (8) do not
    cfg = ModelConfig(**{**base.__dict__, "n_heads": 3, "n_kv": 3,
                         "d_model": 48})
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="n_experts"):
        E.PagedServingEngine(params, cfg, max_seqs=3, mesh=_FakeMesh())


_TP_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro import configs
    from repro.core.types import P16_2
    from repro.models.transformer import ModelConfig, init_params
    from repro.quant.policy import PositPolicy
    from repro.serving import engine as E
    from repro.launch.mesh import make_serving_mesh

    base = configs.get_smoke("olmoe-1b-7b")
    cfg = ModelConfig(**{**base.__dict__,
                         "policy": PositPolicy(kv_cache=P16_2)})
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, cfg.vocab, int(rng.integers(3, 14))
                          ).astype(np.int32), 6) for _ in range(8)]

    ref = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)
    res_ref = ref.run([(p.copy(), n) for p, n in reqs])

    # DP, DPxEP, pure EP: experts split over the model axis, one psum per
    # block — greedy tokens bit-identical to the single-device engine
    for shape in [(4, 1), (2, 2), (1, 4)]:
        mesh = make_serving_mesh(*shape)
        eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                                   table_width=8, prefill_chunk=8,
                                   mesh=mesh)
        res = eng.run([(p.copy(), n) for p, n in reqs])
        assert sorted(res) == sorted(res_ref), shape
        for r in res_ref:
            assert np.array_equal(res[r], res_ref[r]), (shape, r)
    print("MOE-TP-OK")
""")


@pytest.mark.parametrize("path", ["oneshot", "grouped"])
def test_moe_tp_serving_bit_exact_vs_single_device(path):
    """Both EP dispatch branches: the jnp one-hot oracle (default CPU) and
    the sentinel-sort grouped path (interpret-mode kernels) must match the
    single-device engine bit for bit on every mesh layout."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    if path == "grouped":
        env["REPRO_USE_PALLAS"] = "1"
        env["REPRO_PALLAS_INTERPRET"] = "1"
    else:
        env.pop("REPRO_USE_PALLAS", None)
        env.pop("REPRO_PALLAS_INTERPRET", None)
    env.pop("REPRO_FORCE_GATHER", None)
    out = subprocess.run([sys.executable, "-c", _TP_SUBPROCESS], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MOE-TP-OK" in out.stdout


# --------------------------------------------------------------------------
# router projection at storage width (no per-step router decode)
# --------------------------------------------------------------------------
def test_posit_router_routes_through_pw_matmul():
    from repro.core.decode import decode_to_f32

    rng = np.random.default_rng(10)
    d, E = 32, 8
    router = f32_to_posit(jnp.asarray(rng.normal(size=(d, E)), jnp.float32),
                          P16_2)
    from repro.core.array import PositArray
    xt = jnp.asarray(rng.normal(size=(2, 8, d)), jnp.float32)
    got = MOE._router_logits(xt, PositArray(router, P16_2), NONE)
    want = jnp.einsum("gtd,de->gte", xt, decode_to_f32(router, P16_2),
                      preferred_element_type=jnp.float32)
    assert got.shape == (2, 8, E)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
