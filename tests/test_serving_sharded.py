"""Mesh-sharded paged serving: TP/DP spec rules, the on-device sampling
contract (a decode step moves O(max_seqs) ints host<->device, never
logits), and sharded-vs-single-device bit-exactness on a forced 8-device
CPU host (subprocess — the parent process stays single-device)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.types import P16_2
from repro.distributed import sharding as sh
from repro.models.transformer import ModelConfig, init_params
from repro.quant.policy import PositPolicy
from repro.serving import engine as E


def _cfg(**kw):
    return ModelConfig(name="tst-sh", n_layers=2, d_model=32, n_heads=4,
                       n_kv=2, d_ff=64, vocab=50,
                       policy=PositPolicy(kv_cache=P16_2), **kw)


class MockMesh:
    shape = {"data": 4, "model": 2}
    size = 8


# ---- spec rules (no devices needed) --------------------------------------
def test_serving_param_pspecs_megatron_layout():
    cfg = _cfg()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = sh.serving_param_pspecs(shapes, MockMesh())
    flat = {sh._path_str(p): s for (p, _), (_, s) in zip(
        jax.tree_util.tree_flatten_with_path(shapes)[0],
        jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0])}
    wq = next(v for k, v in flat.items() if k.endswith("attn/wq/w"))
    wo = next(v for k, v in flat.items() if k.endswith("attn/wo/w"))
    wd = next(v for k, v in flat.items() if k.endswith("mlp/w_down/w"))
    table = next(v for k, v in flat.items() if k.endswith("embed/table"))
    assert wq[-1] == "model" and wo[-2] == "model"       # column / row
    assert wd[-2] == "model"
    assert table[-2] == "model"                          # vocab 50 % 2 == 0
    # serving never FSDPs: nothing may shard over 'data'
    for k, s in flat.items():
        assert "data" not in str(s), (k, s)


def test_serving_param_pspecs_drops_indivisible_vocab():
    cfg = _cfg()

    class M4:
        shape = {"data": 2, "model": 4}

    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = sh.serving_param_pspecs(shapes, M4())
    flat = {sh._path_str(p): s for (p, _), (_, s) in zip(
        jax.tree_util.tree_flatten_with_path(shapes)[0],
        jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0])}
    table = next(v for k, v in flat.items() if k.endswith("embed/table"))
    assert "model" not in str(table)                     # 50 % 4 != 0


def test_paged_pool_pspecs_pages_over_data_kv_over_model():
    from repro.models.transformer import init_paged_pages
    cfg = _cfg()
    pages = jax.eval_shape(
        lambda: init_paged_pages(cfg, num_pages=8, page_size=4))
    specs = sh.paged_pool_pspecs(pages, MockMesh())
    scanned = specs["scanned"][0]["k_pages"]             # [reps, np, kv, p, d]
    assert scanned == P(None, "data", "model", None, None)


# ---- engine validation ---------------------------------------------------
class _FakeMesh:
    def __init__(self, d, m):
        self.shape = {"data": d, "model": m}


def test_sharded_engine_rejects_indivisible_shapes():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="max_seqs"):
        E.PagedServingEngine(params, cfg, max_seqs=3, mesh=_FakeMesh(2, 1))
    with pytest.raises(ValueError, match="n_kv"):
        E.PagedServingEngine(params, cfg, max_seqs=8, mesh=_FakeMesh(1, 4))


# ---- on-device sampling contract -----------------------------------------
def test_decode_step_transfers_only_token_ids():
    """The jitted step's outputs are the [max_seqs] int32 sampled tokens,
    the [max_seqs] bool NaR flags, and the (donated, device-resident) page
    pools — no [max_seqs, vocab] logits leaf exists for the host to pull
    (the ISSUE-3 acceptance row; still O(max_seqs) after the ISSUE-9
    on-device NaR detector rode its flags onto the same transfer)."""
    from repro.models.transformer import init_paged_pages
    cfg = _cfg()
    max_seqs, page, W = 4, 4, 8
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pages = jax.eval_shape(
        lambda: init_paged_pages(cfg, num_pages=1 + max_seqs * W,
                                 page_size=page))
    step = E._paged_step(cfg, True)
    out = jax.eval_shape(
        step, params,
        jax.ShapeDtypeStruct((max_seqs, 1), jnp.int32), pages,
        jax.ShapeDtypeStruct((max_seqs, W), jnp.int32),
        jax.ShapeDtypeStruct((max_seqs,), jnp.int32),
        jax.ShapeDtypeStruct((max_seqs,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((max_seqs,), jnp.bool_))
    toks, bad, new_pages = out
    assert toks.shape == (max_seqs,) and toks.dtype == jnp.int32
    assert bad.shape == (max_seqs,) and bad.dtype == jnp.bool_
    for leaf in jax.tree_util.tree_leaves(new_pages):
        assert leaf.ndim >= 4, leaf.shape     # page pools only, no logits


def test_engine_never_samples_on_host(monkeypatch):
    """Greedy decode must not touch the host oracle at all."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, cfg.vocab)
    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)

    def boom(row):
        raise AssertionError("host sampling reached on the decode path")

    monkeypatch.setattr(eng, "_sample_host", boom)
    res = eng.run([(np.asarray(prompts[i]), 4) for i in range(4)])
    assert sorted(res) == list(range(4))


def test_device_sampling_matches_host_oracle():
    """Greedy tokens from the on-device step equal _sample_host applied to
    independently computed logits (the oracle role the host sampler keeps)."""
    from repro.models.transformer import forward, init_caches
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    eng = E.PagedServingEngine(params, cfg, max_seqs=2, page_size=4,
                               table_width=8, prefill_chunk=8)
    res = eng.run([(np.asarray(prompts[i]), 1) for i in range(2)])
    caches = init_caches(cfg, 2, 16)
    logits, _, _ = forward(params, cfg, tokens=prompts, caches=caches)
    for i in range(2):
        assert int(res[i][0]) == eng._sample_host(np.asarray(logits[i, -1]))


# ---- 1x1 mesh: the sharded step itself, runnable on one device -----------
def test_sharded_engine_1x1_mesh_matches_unsharded():
    from repro.launch.mesh import make_serving_mesh
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, cfg.vocab, int(rng.integers(3, 12))
                          ).astype(np.int32), 6) for _ in range(6)]
    ref = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)
    res_ref = ref.run([(p.copy(), n) for p, n in reqs])
    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8,
                               mesh=make_serving_mesh(1, 1))
    res = eng.run([(p.copy(), n) for p, n in reqs])
    for r in res_ref:
        assert np.array_equal(res[r], res_ref[r]), r
    assert any(k[0] == "sharded_paged_step" for k in E.STEP_TRACES)


# ---- the acceptance row: forced 8-device host, subprocess ----------------
_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.types import P16_2
    from repro.models.transformer import ModelConfig, init_params
    from repro.quant.policy import PositPolicy
    from repro.serving import engine as E
    from repro.launch.mesh import make_serving_mesh

    cfg = ModelConfig(name="tst-sh8", n_layers=2, d_model=32, n_heads=4,
                      n_kv=2, d_ff=64, vocab=50,
                      policy=PositPolicy(kv_cache=P16_2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, cfg.vocab, int(rng.integers(3, 14))
                          ).astype(np.int32), 8) for _ in range(12)]

    ref = E.PagedServingEngine(params, cfg, max_seqs=8, page_size=4,
                               table_width=8, prefill_chunk=8)
    res_ref = ref.run([(p.copy(), n) for p, n in reqs])

    # pure DP (8, 1): structurally bit-exact (row-independent math per slot)
    # and DP x TP (4, 2): Megatron psums + vocab-parallel embed/unembed
    for shape in [(8, 1), (4, 2)]:
        mesh = make_serving_mesh(*shape)
        eng = E.PagedServingEngine(params, cfg, max_seqs=8, page_size=4,
                                   table_width=8, prefill_chunk=8, mesh=mesh)
        res = eng.run([(p.copy(), n) for p, n in reqs])
        assert sorted(res) == sorted(res_ref), (shape, sorted(res))
        for r in res_ref:
            assert np.array_equal(res[r], res_ref[r]), (shape, r)

        # a decode step returns [max_seqs] int32 token ids, [max_seqs]
        # bool NaR flags and page pools only — no logits-shaped leaf ever
        # crosses to the host
        toks, bad, pages = jax.eval_shape(
            eng._step_fn, params,
            jax.ShapeDtypeStruct((8, 1), jnp.int32), eng.pages,
            jax.ShapeDtypeStruct((8, 8), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.bool_))
        assert toks.shape == (8,) and toks.dtype == jnp.int32
        assert bad.shape == (8,) and bad.dtype == jnp.bool_
        for leaf in jax.tree_util.tree_leaves(pages):
            assert leaf.ndim >= 4, leaf.shape

    # zero steady-state retrace: a fresh engine on the same mesh reuses the
    # shared jitted step for the whole drain
    before = dict(E.STEP_TRACES)
    mesh = make_serving_mesh(8, 1)
    eng2 = E.PagedServingEngine(params, cfg, max_seqs=8, page_size=4,
                                table_width=8, prefill_chunk=8, mesh=mesh)
    eng2.run([(p.copy(), n) for p, n in reqs])
    assert dict(E.STEP_TRACES) == before, (before, dict(E.STEP_TRACES))
    assert any(k[0] == "sharded_paged_step" for k in E.STEP_TRACES)
    print("SHARDED-OK")
""")


def test_sharded_vs_single_device_bit_exact_8dev():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED-OK" in out.stdout


# ---- q/k norm and the routing record under TP: forced 4-device host ------
_MOE_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.types import P16_2
    from repro.models.transformer import ModelConfig, MoEConfig, init_params
    from repro.quant.policy import PositPolicy
    from repro.serving import engine as E
    from repro.launch.mesh import make_serving_mesh

    # full-width q/k norm (one psum of both shards' sums of squares) and
    # un-renormalized top-k, as OLMoE serves them
    cfg = ModelConfig(name="tst-sh-moe", n_layers=2, d_model=32, n_heads=4,
                      n_kv=2, d_ff=16, vocab=50, qk_norm=True,
                      tie_embeddings=False,
                      moe=MoEConfig(n_experts=8, top_k=2, norm_topk=False),
                      policy=PositPolicy(kv_cache=P16_2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    # scales away from one: a shard that normed or scaled with the wrong
    # columns would show
    key = jax.random.PRNGKey(1)
    attn = params["scanned"][0]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):
        s = attn[name]["scale"]
        attn[name]["scale"] = s + 0.5 * jax.random.normal(
            jax.random.fold_in(key, i), s.shape)
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, cfg.vocab, int(rng.integers(3, 14))
                          ).astype(np.int32), 6) for _ in range(6)]

    def drain(mesh):
        eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                                   table_width=8, prefill_chunk=8, mesh=mesh,
                                   record_logits=True)
        return eng, eng.run([(p.copy(), n) for p, n in reqs])

    ref, res_ref = drain(None)
    eng, res = drain(make_serving_mesh(2, 2))
    assert any(k[0] == "sharded_paged_step" for k in E.STEP_TRACES)
    assert sorted(res) == sorted(res_ref)
    for r in res_ref:
        assert np.array_equal(res[r], res_ref[r]), r
        np.testing.assert_allclose(np.stack(eng.token_logits[r]),
                                   np.stack(ref.token_logits[r]),
                                   rtol=1e-5, atol=1e-5)
        got, want = eng.routing(r), ref.routing(r)
        assert want.shape[1:] == (cfg.n_layers, 2) and (want >= 0).all()
        assert np.array_equal(got, want), r
    assert eng.stats()["moe_pairs"] == ref.stats()["moe_pairs"] > 0
    print("SHARDED-MOE-OK")
""")


def test_sharded_moe_qk_norm_matches_unsharded_4dev():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MOE_SUBPROCESS], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED-MOE-OK" in out.stdout
