"""Paged KV cache + continuous-batching engine: correctness vs the dense
oracle (bit-exact logits), eviction/slot-reuse, paged flash-decode parity,
chunked prefill, and the zero-retrace guarantees."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.types import P8_2, P16_2
from repro.models.transformer import (ModelConfig, assemble_paged_caches,
                                      extract_paged_pages, forward,
                                      init_caches, init_params,
                                      init_paged_pages)
from repro.quant.policy import PositPolicy
from repro.serving import engine as E
from repro.serving.kv_cache import append_kv, init_cache, materialize_kv
from repro.serving.paged_kv import gather_kv, paged_append_kv


def _cfg(pcfg, **kw):
    return ModelConfig(name="tst", n_layers=2, d_model=32, n_heads=4,
                       n_kv=2, d_ff=64, vocab=50,
                       policy=PositPolicy(kv_cache=pcfg), **kw)


def _sequential_table(B, W):
    pt = np.zeros((B, W), np.int32)
    pt[:] = 1 + np.arange(B * W).reshape(B, W)
    return jnp.asarray(pt)


@pytest.mark.parametrize("pcfg", [None, P16_2, P8_2],
                         ids=["float", "p16", "p8"])
def test_paged_vs_dense_logits_bit_exact(pcfg):
    """Same batch through the dense cache and the paged pool: prefill and
    decode logits must agree bit for bit (same ops, same element order —
    the gathered page view is position-identical to the dense buffer)."""
    cfg = _cfg(pcfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S, page, W = 2, 6, 4, 8
    max_len = page * W
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)

    dense = init_caches(cfg, B, max_len)
    ld, _, dense = forward(params, cfg, tokens=toks, caches=dense)

    pages = init_paged_pages(cfg, num_pages=1 + B * W, page_size=page)
    pt = _sequential_table(B, W)
    caches = assemble_paged_caches(pages, pt, jnp.zeros((B,), jnp.int32),
                                   jnp.full((B,), S, jnp.int32))
    lp, _, caches = forward(params, cfg, tokens=toks, caches=caches)
    pages = extract_paged_pages(caches)
    assert jnp.array_equal(ld, lp), "prefill logits diverge"

    tok = jnp.argmax(ld[:, -1], -1)[:, None].astype(jnp.int32)
    ld2, _, dense = forward(params, cfg, tokens=tok, caches=dense)
    caches = assemble_paged_caches(pages, pt, jnp.full((B,), S, jnp.int32),
                                   jnp.ones((B,), jnp.int32))
    lp2, _, _ = forward(params, cfg, tokens=tok, caches=caches)
    assert jnp.array_equal(ld2, lp2), "decode logits diverge"


def _decode_case(pcfg, *, seq_lens, n_kv=2, G=2, D=16, page=8, W=4,
                 shuffled=False, nar_pages=False, window=None, seed=3):
    """q, K/V pools and a page table for paged_flash_decode.  shuffled: the
    table names the pool's pages in a random order.  nar_pages: every entry
    past a sequence's length, or wholly before its window, names one page
    of NaR (NaN for float) bits, which a kernel that reads it cannot hide;
    the clean table is returned beside it for the reference."""
    from repro.core.convert import f32_to_posit

    rng = np.random.default_rng(seed)
    B = len(seq_lens)
    seq_lens = np.asarray(seq_lens, np.int32)
    pages = 1 + np.arange(B * W)
    if shuffled:
        pages = rng.permutation(pages)
    clean = pt = pages.reshape(B, W).astype(np.int32)
    n_pool = 1 + B * W
    if nar_pages:
        start = np.arange(W)[None] * page
        dead = start >= seq_lens[:, None]
        if window is not None:
            dead |= start + page <= seq_lens[:, None] - window
        pt = np.where(dead, n_pool, pt).astype(np.int32)
        n_pool += 1
    kd = rng.normal(size=(n_pool, n_kv, page, D)).astype(np.float32)
    vd = rng.normal(size=(n_pool, n_kv, page, D)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, n_kv * G, D)).astype(np.float32))
    if pcfg is not None:
        enc = jax.jit(f32_to_posit, static_argnums=1)
        kp, vp = enc(jnp.asarray(kd), pcfg), enc(jnp.asarray(vd), pcfg)
    else:
        kp, vp = jnp.asarray(kd), jnp.asarray(vd)
    if nar_pages:
        bad = jnp.iinfo(kp.dtype).min if pcfg is not None else jnp.nan
        kp, vp = kp.at[-1].set(bad), vp.at[-1].set(bad)
    return q, kp, vp, pt, clean, seq_lens


# Cases beyond the original mixed-length ones.  decode_pages_per_block
# pages make a block of ~128 tokens: the lengths cover 0, 1, a block
# boundary, one past it and the whole table; the widths are ones the block
# does not divide (8 pages into 20 or 12, 4 into 5); page sizes 8, 16 and
# 32; groups 1 and 3; head dims that fold two or four tokens into a
# 128-lane row (64, 32), that fill one (128) and that are padded to one
# (48); and tables whose dead entries name a NaR page.
_BLOCKS = dict(seq_lens=[0, 1, 128, 129, 160], shuffled=True, D=64)
_P8W20 = dict(_BLOCKS, page=8, W=20, G=1)
_P16W12 = dict(_BLOCKS, seq_lens=[0, 1, 128, 129, 192], page=16, W=12, G=3)
_P32W5 = dict(_BLOCKS, page=32, W=5, G=3)
_NAR = dict(_P16W12, seq_lens=[1, 16, 128, 129, 150], nar_pages=True)
_FMTS = {"float": None, "p16": P16_2, "p8": P8_2}


def _cases(named, windowed=False):
    """pytest params ([window,] pcfg, case) of (name, formats, case)."""
    return [pytest.param(*([kw["window"]] if windowed else []), _FMTS[f], kw,
                         id=f"{f}-{nm}")
            for nm, fmts, kw in named for f in fmts]


@pytest.mark.parametrize("pcfg,case", [
    pytest.param(None, None, id="float"),
    pytest.param(P16_2, None, id="p16"),
    pytest.param(P8_2, None, id="p8"),
    *_cases([
        ("page8-W20-g1-d64", ("p16", "p8"), _P8W20),
        ("page16-W12-g3-d64", ("float", "p16", "p8"), _P16W12),
        ("page32-W5-g3-d64", ("float", "p16", "p8"), _P32W5),
        ("page16-W12-g1-d128", ("p16", "p8"),
         dict(_P16W12, seq_lens=[1, 127, 128, 129, 192], G=1, D=128)),
        ("page16-W9-g3-d48", ("float", "p16"),
         dict(_P16W12, seq_lens=[0, 17, 128, 129, 144], W=9, D=48)),
        ("page16-W12-g2-d32-kv4", ("p16",), dict(_P16W12, G=2, D=32,
                                                 n_kv=4)),
        ("nar-pages", ("float", "p16", "p8"), _NAR),
    ])])
def test_paged_flash_decode_vs_materialized_dense_attention(pcfg, case):
    """The Pallas paged-gather decode kernel (interpret mode) vs the
    materialize_kv + dense flash-attention oracle at mixed lengths.  A
    sequence of length 0 reads nothing and returns zeros."""
    from repro.kernels.flash_attention import paged_flash_decode
    from repro.kernels.ref import flash_attention_ref

    q, kp, vp, pt, _, seq_lens = _decode_case(
        pcfg, **(case or dict(seq_lens=[5, 17, 32])))
    B, H, D = q.shape
    n_kv = kp.shape[1]
    G = H // n_kv
    W = pt.shape[1]

    out = paged_flash_decode(q, kp, vp, jnp.asarray(pt),
                             jnp.asarray(seq_lens), cfg_kv=pcfg,
                             interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    for i in range(B):
        if seq_lens[i] == 0:
            assert not np.asarray(out[i]).any()
            continue
        # materialize this sequence's pages densely, run the ref oracle
        kk = np.concatenate([np.asarray(kp)[pt[i, j]] for j in range(W)],
                            axis=1)[:, :seq_lens[i]]
        vv = np.concatenate([np.asarray(vp)[pt[i, j]] for j in range(W)],
                            axis=1)[:, :seq_lens[i]]
        # one oracle call per sequence, batched over its KV heads (the
        # group's query heads are that head's rows)
        ref = flash_attention_ref(q[i].reshape(n_kv, G, D), jnp.asarray(kk),
                                  jnp.asarray(vv), cfg_kv=pcfg, causal=False)
        got = np.asarray(out[i]).reshape(n_kv, G, D)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-6,
                                   atol=2e-6)


@pytest.mark.parametrize("window,pcfg,case", [
    *[pytest.param(w, _FMTS[f], None, id=f"{w}-{f}")
      for w in (4, 16) for f in ("float", "p16")],
    # windows smaller and larger than a block of ~128 tokens
    *_cases([
        ("w40-page16-W12-g3-d64", ("p16", "p8"), dict(_P16W12, window=40)),
        ("w200-page16-W12-g3-d64", ("float", "p16", "p8"),
         dict(_P16W12, window=200)),
        ("w100-page8-W20-g1-d64", ("p16", "p8"), dict(_P8W20, window=100)),
        ("w130-page32-W5-g3-d64", ("p16", "p8"), dict(_P32W5, window=130)),
        ("w20-nar-pages", ("float", "p16", "p8"), dict(_NAR, window=20)),
    ], windowed=True)])
def test_paged_flash_decode_window_matches_gathered_reference(window, pcfg,
                                                              case):
    """Windowed (local-attention) decode used to fall off the paged kernel
    onto the dense gather_kv path; the kernel now masks the window itself,
    skips the blocks wholly before it, and must match the gathered
    blockwise reference at mixed lengths."""
    from repro.kernels.flash_attention import paged_flash_decode
    from repro.models.blocks import blockwise_attention

    q, kp, vp, pt, clean, seq_lens = _decode_case(
        pcfg, **(case or dict(seq_lens=[3, 17, 32], seed=11)))
    n_kv = kp.shape[1]
    seq_lens = jnp.asarray(seq_lens)

    out = paged_flash_decode(q, kp, vp, jnp.asarray(pt), seq_lens,
                             cfg_kv=pcfg, window=window, interpret=True)

    # the reference gathers every table entry, so it gets the clean table
    pt = jnp.asarray(clean)
    if pcfg is not None:
        from repro.core.array import PositArray
        cache = {"k_pages": PositArray(kp, pcfg),
                 "v_pages": PositArray(vp, pcfg), "page_table": pt}
    else:
        cache = {"k_pages": kp, "v_pages": vp, "page_table": pt}
    k, v = gather_kv(cache)
    ref = blockwise_attention(q[:, :, None, :], k, v, n_kv=n_kv, causal=True,
                              q_offset=seq_lens - 1, window=window,
                              kv_len=seq_lens)
    live = np.asarray(seq_lens) > 0
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out)[~live].any()
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref[:, :, 0, :])[live],
                               rtol=2e-6, atol=2e-6)


def test_dense_steps_donate_cache_buffers():
    """_dense_steps used to jit without donate_argnums, holding two full KV
    caches live per step; the decode step must now alias the new cache onto
    the donated input buffers (and invalidate the donated array)."""
    params, cfg, prompts = _engine_model()
    pf, dc = E._dense_steps(cfg)
    caches = init_caches(cfg, 4, 16, dtype=jnp.dtype(cfg.dtype))
    logits, caches = pf(params, prompts, caches)

    def kbuf(c):
        k = c["scanned"][0]["k"]
        return k.bits if hasattr(k, "bits") else k

    kbuf(caches).block_until_ready()
    ptr = kbuf(caches).unsafe_buffer_pointer()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    donated = caches
    logits, caches = dc(params, tok, caches)
    kbuf(caches).block_until_ready()
    assert kbuf(caches).unsafe_buffer_pointer() == ptr, \
        "decode step did not reuse the donated KV buffer"
    with pytest.raises(RuntimeError):
        np.asarray(kbuf(donated))            # donated input is dead


def test_paged_append_drops_masked_writes_out_of_bounds():
    """Masked scatter rows must vanish, not wrap into the last page (the
    -1-index clobber this PR fixed)."""
    cfg = _cfg(P16_2)
    pages = init_paged_pages(cfg, num_pages=4, page_size=4)
    layer = pages["scanned"][0]     # stacked [reps=2, ...]
    one = jax.tree_util.tree_map(lambda x: x[0], layer)
    B, W = 2, 1
    pt = jnp.asarray([[3], [2]], jnp.int32)   # last page owned by seq 0
    cache = {"k_pages": one["k_pages"], "v_pages": one["v_pages"],
             "page_table": pt, "seq_lens": jnp.zeros((B,), jnp.int32),
             "num_new": jnp.asarray([2, 0], jnp.int32)}   # seq 1 inactive
    k = jnp.ones((B, cfg.n_kv, 2, cfg.hd), jnp.float32)
    new = paged_append_kv(cache, k, 2.0 * k)
    # seq 1 wrote nothing anywhere: pages 1, 2 and the garbage page stay 0
    bits = new["k_pages"].bits
    assert (bits[2] == 0).all() and (bits[1] == 0).all()
    assert (bits[3][:, :2] != 0).any()        # seq 0's write landed
    assert int(new["seq_lens"][1]) == 0


@pytest.mark.parametrize("pcfg", [None, P16_2], ids=["float", "p16"])
def test_dense_chunked_prefill_no_clobber(pcfg):
    """append_kv's prefill-sized fast path used to write at static offset
    0, clobbering earlier tokens when a chunked prefill hit a part-full
    cache (appends are one masked-write path now)."""
    rng = np.random.default_rng(0)
    cache = init_cache(2, 2, 16, 8, pcfg)
    k = jnp.asarray(rng.normal(size=(2, 2, 12, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 12, 8)), jnp.float32)
    whole = append_kv(cache, k, v)
    # prefill-sized chunks (6*4 >= 16) into a part-full cache
    chunked = append_kv(cache, k[:, :, :6], v[:, :, :6])
    chunked = append_kv(chunked, k[:, :, 6:], v[:, :, 6:])
    k1, v1 = materialize_kv(whole)
    k2, v2 = materialize_kv(chunked)
    assert int(chunked["length"]) == 12
    assert jnp.array_equal(k1, k2) and jnp.array_equal(v1, v2)


def _engine_model():
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, cfg.vocab)
    return params, cfg, prompts


def test_engine_matches_dense_generate():
    params, cfg, prompts = _engine_model()
    max_new = 8
    dense = np.asarray(E.generate(params, cfg, prompts, max_new, max_len=32))
    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)
    res = eng.run([(np.asarray(prompts[i]), max_new) for i in range(4)])
    for i in range(4):
        assert np.array_equal(res[i], dense[i]), i


def test_engine_slot_reuse_more_requests_than_slots():
    params, cfg, prompts = _engine_model()
    max_new = 8
    dense = np.asarray(E.generate(params, cfg, prompts, max_new, max_len=32))
    eng = E.PagedServingEngine(params, cfg, max_seqs=2, page_size=4,
                               table_width=8, prefill_chunk=8)
    res = eng.run([(np.asarray(prompts[i % 4]), max_new) for i in range(6)])
    assert sorted(res) == list(range(6))
    assert eng.counters["completed"] == 6 and eng.active == 0
    # every page is either back on the free stack or resident in the
    # prefix cache (idle, evictable) — none leaked, none doubly owned
    assert len(eng.free_pages) + eng.cached_pages == eng.num_pages - 1
    assert eng.stats()["prefix_hits"] >= 2   # repeated prompts hit warm
    for i in range(6):
        assert np.array_equal(res[i], dense[i % 4]), i


def test_engine_eviction_preserves_outputs():
    """A pool too small for the full workload forces preemption; evicted
    requests must resume (prompt + generated so far) and still produce the
    dense engine's exact tokens."""
    params, cfg, _ = _engine_model()
    prompts = jax.random.randint(jax.random.PRNGKey(2), (3, 10), 0,
                                 cfg.vocab)
    dense = np.asarray(E.generate(params, cfg, prompts, 12, max_len=32))
    eng = E.PagedServingEngine(params, cfg, max_seqs=3, page_size=4,
                               table_width=8, num_pages=10, prefill_chunk=16)
    res = eng.run([(np.asarray(prompts[i]), 12) for i in range(3)])
    assert eng.counters["preempted"] >= 1, \
        "workload did not exercise preemption"
    for i in range(3):
        assert np.array_equal(res[i], dense[i]), i


def test_generate_zero_retrace_across_calls():
    """generate() used to rebuild its jit wrappers per call; the hoisted
    steps must not retrace for repeated calls (same shapes, different
    max_new)."""
    params, cfg, prompts = _engine_model()
    E.generate(params, cfg, prompts, 3, max_len=24)
    before = dict(E.STEP_TRACES)
    E.generate(params, cfg, prompts, 6, max_len=24)    # longer decode loop
    E.generate(params, cfg, prompts, 4, max_len=24)
    after = dict(E.STEP_TRACES)
    assert after == before, (before, after)


def test_paged_engine_zero_retrace_steady_state():
    params, cfg, prompts = _engine_model()
    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=8)
    eng.run([(np.asarray(prompts[i]), 4) for i in range(4)])
    before = dict(E.STEP_TRACES)
    # same engine, new traffic: no new traces at all (finished accumulates
    # across runs, so the second drain reports rids 0..7)
    eng2_res = eng.run([(np.asarray(prompts[i]), 4) for i in range(4)])
    assert sorted(eng2_res) == list(range(8))
    # a fresh engine shares the per-config jitted step: still no retrace
    eng3 = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                                table_width=8, prefill_chunk=8)
    eng3.run([(np.asarray(prompts[i]), 4) for i in range(4)])
    after = dict(E.STEP_TRACES)
    assert after == before, (before, after)


def test_engine_record_logits_first_token_matches_dense_prefill():
    """record_logits keeps the logits behind every generated token: the
    first are the dense cached forward's last prompt position, each later
    one the dense decode step fed the previous token, and the argmax of
    each is the token the engine emitted."""
    params, cfg, prompts = _engine_model()
    max_new = 3
    eng = E.PagedServingEngine(params, cfg, max_seqs=4, page_size=4,
                               table_width=8, prefill_chunk=4,
                               record_logits=True)
    res = eng.run([(np.asarray(prompts[i]), max_new) for i in range(4)])
    assert sorted(eng.token_logits) == list(range(4))
    caches = init_caches(cfg, 4, 32)
    want, _, caches = forward(params, cfg, tokens=prompts, caches=caches)
    want = [want[:, -1]]
    for k in range(1, max_new):
        tok = jnp.asarray(np.stack([res[i][k - 1] for i in range(4)]))
        nxt, _, caches = forward(params, cfg, tokens=tok[:, None],
                                 caches=caches)
        want.append(nxt[:, -1])
    for i in range(4):
        got = eng.token_logits[i]
        assert len(got) == max_new
        for k in range(max_new):
            assert got[k].shape == (cfg.vocab,)
            np.testing.assert_allclose(got[k], np.asarray(want[k][i]),
                                       rtol=1e-5, atol=1e-5)
            assert int(np.argmax(got[k])) == int(res[i][k])


def test_paged_step_cache_keys_on_kernel_dispatch(monkeypatch):
    """Kernel dispatch is decided at trace time, so an engine built with the
    Pallas path switched on must not share the step traced without it —
    nor may generate()'s dense steps."""
    params, cfg, _ = _engine_model()
    kw = dict(max_seqs=4, page_size=4, table_width=8, prefill_chunk=8)
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    ref = E.PagedServingEngine(params, cfg, **kw)
    ref_dense = E._dense_steps(cfg)
    assert E.PagedServingEngine(params, cfg, **kw)._step_fn is ref._step_fn
    assert E._dense_steps(cfg) is ref_dense
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert E.PagedServingEngine(params, cfg, **kw)._step_fn \
        is not ref._step_fn
    assert E._dense_steps(cfg) is not ref_dense
