"""Prefix cache subsystem: content-addressed radix index, PagePool
refcount/pinning invariants (hypothesis-driven), warm-vs-cold bit-exactness
across float/p8/p16 pages, copy-on-write, dedup, LRU eviction ordered
before preemption, and DP-sharded warm/cold parity in a subprocess."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from repro.core.types import P8_2, P16_2
from repro.models.transformer import ModelConfig, init_params
from repro.quant.policy import PositPolicy
from repro.serving import engine as E
from repro.serving.paged_kv import PagePool
from repro.serving.prefix_cache import RadixIndex, chunk_digest, root_digest


def _cfg(pcfg, **kw):
    return ModelConfig(name="tst-px", n_layers=2, d_model=32, n_heads=4,
                       n_kv=2, d_ff=64, vocab=50,
                       policy=PositPolicy(kv_cache=pcfg), **kw)


def _engine(params, cfg, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("table_width", 8)
    kw.setdefault("prefill_chunk", 8)
    return E.PagedServingEngine(params, cfg, **kw)


# ==========================================================================
# radix index
# ==========================================================================
def test_radix_index_lookup_insert_roundtrip():
    idx = RadixIndex("model-a|p16|page=4", page_size=4)
    toks = np.arange(13, dtype=np.int32)
    n1, _ = idx.insert(idx.root, toks[:4], page=7, clock=1)
    n2, _ = idx.insert(n1, toks[4:8], page=9, clock=1)
    assert idx.probe(toks) == 8            # 3rd page partial: not cached
    pages, node = idx.lookup(toks, clock=2)
    assert pages == [7, 9] and node is n2
    # divergent second page: only the first matches
    other = toks.copy()
    other[5] = 49
    pages, node = idx.lookup(other, clock=3)
    assert pages == [7] and node is n1
    # shorter than a page: nothing to match
    assert idx.probe(toks[:3]) == 0


def test_radix_index_insert_dedups_identical_chunk():
    idx = RadixIndex("k", page_size=4)
    chunk = np.asarray([1, 2, 3, 4], np.int32)
    n1, existing = idx.insert(idx.root, chunk, page=3, clock=0)
    assert existing is None
    n2, existing = idx.insert(idx.root, chunk, page=5, clock=1)
    assert n2 is n1 and existing == 3      # caller adopts page 3, frees 5
    assert len(idx) == 1


def test_radix_index_keyed_per_model_and_format():
    """The digest chain is rooted in the model/format/page key: identical
    token chunks under different keys can never alias."""
    a = root_digest("gemma|p16|page=64")
    b = root_digest("gemma|p8|page=64")
    chunk = np.arange(64, dtype=np.int32)
    assert a != b
    assert chunk_digest(a, chunk) != chunk_digest(b, chunk)
    # chained: same chunk under different parents differs too
    assert (chunk_digest(chunk_digest(a, chunk), chunk)
            != chunk_digest(a, chunk))


def test_radix_index_evicts_lru_leaves_first():
    idx = RadixIndex("k", page_size=2)
    t = np.asarray([1, 2, 3, 4, 5, 6], np.int32)
    n1, _ = idx.insert(idx.root, t[:2], page=1, clock=1)
    n2, _ = idx.insert(n1, t[2:4], page=2, clock=5)
    idx.insert(n2, t[4:6], page=3, clock=3)
    # page 1 is oldest but interior: the LRU *leaf* (page 3) dies first,
    # then page 2, then page 1 — a cached chain never dangles
    assert idx.evict_lru(lambda p: True) == 3
    assert idx.evict_lru(lambda p: True) == 2
    assert idx.evict_lru(lambda p: True) == 1
    assert idx.evict_lru(lambda p: True) is None


def test_radix_index_eviction_respects_live_refs():
    idx = RadixIndex("k", page_size=2)
    n1, _ = idx.insert(idx.root, np.asarray([1, 2], np.int32), 1, clock=0)
    idx.insert(n1, np.asarray([3, 4], np.int32), 2, clock=1)
    # leaf page 2 is live -> nothing evictable (parent is interior)
    assert idx.evict_lru(lambda p: p != 2) is None
    assert idx.evict_lru(lambda p: True) == 2


# ==========================================================================
# PagePool allocator invariants (satellite: hypothesis property tests)
# ==========================================================================
def _check_invariants(pool: PagePool):
    free = pool.free_list
    live = set(pool._ref)
    cached = set(pool._cached)
    assert 0 not in free and 0 not in live and 0 not in cached, \
        "the reserved garbage page entered circulation"
    assert len(set(free)) == len(free), "free stack holds a duplicate"
    assert not (set(free) & (live | cached)), "free page is live/cached"
    assert all(v >= 1 for v in pool._ref.values()), "non-positive refcount"
    assert len(free) + len(live | cached) == pool.num_pages - 1, \
        "pages leaked or double-counted"


def _drive(pool: PagePool, ops):
    """Interpret a random op stream against the pool, asserting invariants
    after every op.  Invalid transitions must raise ValueError (double
    free, negative refcount, garbage-page ops) and change nothing."""
    held = []                  # pages with refs we hold
    cached = []
    for opcode, arg in ops:
        try:
            if opcode == 0:
                pg = pool.try_alloc()
                if pg is not None:
                    assert pg != 0
                    held.append(pg)
            elif opcode == 1 and held:
                pool.incref(held[arg % len(held)])
                held.append(held[arg % len(held)])
            elif opcode == 2 and held:
                pg = held.pop(arg % len(held))
                pool.decref(pg)
            elif opcode == 3 and held:
                pg = held[arg % len(held)]
                pool.cache(pg)
                if pg not in cached:
                    cached.append(pg)
            elif opcode == 4 and cached:
                pool.uncache(cached.pop(arg % len(cached)))
            elif opcode == 5:
                # invalid: decref a page we hold no reference to
                free = pool.free_list
                if free:
                    with pytest.raises(ValueError):
                        pool.decref(free[arg % len(free)])
            elif opcode == 6:
                for bad in (pool.incref, pool.decref, pool.cache,
                            pool.uncache):
                    with pytest.raises(ValueError):
                        bad(0)             # the garbage page never moves
        finally:
            _check_invariants(pool)
    return held, cached


def test_page_pool_random_walk_deterministic():
    """No-hypothesis fallback: a long seeded op stream (CI also runs the
    hypothesis version below)."""
    rng = np.random.default_rng(0)
    pool = PagePool(17)
    ops = [(int(rng.integers(0, 7)), int(rng.integers(0, 1 << 30)))
           for _ in range(2000)]
    held, cached = _drive(pool, ops)
    # drain: refs then pins; everything must return to the free stack
    for pg in held:
        pool.decref(pg)
    for pg in list(pool._cached):
        pool.uncache(pg)
    _check_invariants(pool)
    assert pool.n_free == pool.num_pages - 1


def test_page_pool_alloc_free_roundtrip_preserves_count():
    pool = PagePool(9)
    n0 = pool.n_free
    pages = [pool.try_alloc() for _ in range(n0)]
    assert pool.try_alloc() is None and pool.n_free == 0
    for pg in pages:
        pool.decref(pg)
    assert pool.n_free == n0
    assert sorted(pool.free_list) == sorted(pages)


def test_page_pool_cached_page_survives_decref_until_uncache():
    pool = PagePool(5)
    pg = pool.try_alloc()
    pool.cache(pg)
    pool.decref(pg)
    assert pool.n_free == 3 and pool.n_evictable == 1
    assert pool.is_idle(pg) and pool.is_cached(pg)
    pool.incref(pg)                        # prefix hit revives it
    assert pool.ref_count(pg) == 1 and pool.n_evictable == 0
    pool.decref(pg)
    assert pool.uncache(pg) is True        # eviction frees it
    assert pool.n_free == 4
    with pytest.raises(ValueError):
        pool.decref(pg)                    # double free


try:
    import hypothesis
    from hypothesis import given, settings, strategies as st

    @given(ops=st.lists(st.tuples(st.integers(0, 6),
                                  st.integers(0, 1 << 30)),
                        max_size=300))
    @settings(max_examples=100, deadline=None)
    def test_page_pool_invariants_hypothesis(ops):
        _drive(PagePool(11), ops)
except ImportError:                         # pragma: no cover
    pass                                    # deterministic walk still runs


# ==========================================================================
# warm vs cold engine bit-exactness
# ==========================================================================
def _shared_prefix_reqs(vocab, n_req=4, prefix_len=8, suffix_len=4,
                        max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len).astype(np.int32)
    return [(np.concatenate([prefix,
                             rng.integers(0, vocab,
                                          suffix_len).astype(np.int32)]),
             max_new) for _ in range(n_req)]


@pytest.mark.parametrize("pcfg", [None, P16_2, P8_2],
                         ids=["float", "p16", "p8"])
def test_warm_vs_cold_bit_identical(pcfg):
    """Greedy tokens from cache-hit (warm) prefill must equal the cold
    engine's bit for bit: shared pages hold exactly the bits a cold
    prefill would recompute, and prefill restarts at the first uncached
    token with q_offset handled in-kernel."""
    cfg = _cfg(pcfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _shared_prefix_reqs(cfg.vocab)
    cold = _engine(params, cfg, prefix_cache=False)
    res_cold = cold.run([(p.copy(), n) for p, n in reqs])
    assert cold.stats()["prefix_hit_tokens"] == 0

    eng = _engine(params, cfg)
    res1 = eng.run([(p.copy(), n) for p, n in reqs])
    for r in res_cold:
        assert np.array_equal(res1[r], res_cold[r]), ("first drain", r)

    res2 = eng.run([(p.copy(), n) for p, n in reqs])     # warm
    st = eng.stats()
    assert st["prefix_hits"] >= len(reqs), st
    assert st["prefix_hit_tokens"] > 0
    for k in range(len(reqs)):
        assert np.array_equal(res2[k + len(reqs)], res_cold[k]), \
            ("warm drain", k)


def test_disjoint_prompts_no_false_sharing():
    """Requests sharing no page-aligned prefix must never hit the cache
    (the digest chain covers the whole prefix, so equal later chunks with
    different openings cannot alias)."""
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(params, cfg)
    # same tail chunk, different first token: chained digests diverge
    base = np.arange(12, dtype=np.int32) % cfg.vocab
    other = base.copy()
    other[0] = (base[0] + 1) % cfg.vocab
    eng.run([(base, 4)])
    eng.run([(other, 4)])
    st = eng.stats()
    assert st["prefix_hits"] == 0 and st["prefix_hit_tokens"] == 0
    assert st["deduped_pages"] == 0


def test_fully_cached_aligned_prompt_cow():
    """A page-aligned fully cached prompt keeps every shared page and
    re-feeds only the final token; its mid-page write must copy-on-write,
    leaving the shared page intact for a third identical request."""
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(8, dtype=np.int32)       # exactly 2 pages of 4
    cold = _engine(params, cfg, prefix_cache=False)
    ref = cold.run([(prompt.copy(), 5)])[0]

    eng = _engine(params, cfg)
    r0 = eng.run([(prompt.copy(), 5)])[0]
    r1 = eng.run([(prompt.copy(), 5)])[1]
    r2 = eng.run([(prompt.copy(), 5)])[2]
    st = eng.stats()
    assert st["cow_copies"] >= 2, st
    assert st["prefix_hit_tokens"] >= 2 * (len(prompt) - 1)
    for r in (r0, r1, r2):
        assert np.array_equal(r, ref)


def test_concurrent_identical_prompts_dedup_to_shared_pages():
    """Two identical prompts admitted cold in the same batch prefill
    privately but converge on one copy at registration (adoption frees
    the duplicate — contents are bit-identical by construction)."""
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(12, dtype=np.int32)
    cold = _engine(params, cfg, prefix_cache=False)
    ref = cold.run([(prompt.copy(), 4), (prompt.copy(), 4)])

    eng = _engine(params, cfg)
    res = eng.run([(prompt.copy(), 4), (prompt.copy(), 4)])
    st = eng.stats()
    assert st["deduped_pages"] >= 2, st
    for r in ref:
        assert np.array_equal(res[r], ref[r]), r
    # pages either free or cached afterwards; dedup means strictly fewer
    # resident pages than two private copies would hold
    assert len(eng.free_pages) + eng.cached_pages == eng.num_pages - 1


def test_eviction_frees_pages_before_preemption():
    """Satellite regression: when idle cached prefix pages can cover a
    new allocation, they are LRU-evicted and NO live sequence is
    preempted (the old engine's only pressure valve)."""
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    pa = rng.integers(0, cfg.vocab, 8).astype(np.int32)    # 2 full pages
    pc = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    pb = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    cold = _engine(params, cfg, prefix_cache=False, max_seqs=2,
                   num_pages=11, admit_threshold=0)
    ref = cold.run([(pa.copy(), 4), (pc.copy(), 16), (pb.copy(), 4)])
    assert cold.counters["preempted"] == 0   # workload fits without cache

    # 10 usable pages: A (2 cached after retiring) + C live (6 at peak) +
    # B (4) only fit if A's cached pages are evicted, not by preempting C
    eng = _engine(params, cfg, max_seqs=2, num_pages=11, admit_threshold=0)
    res = eng.run([(pa.copy(), 4), (pc.copy(), 16), (pb.copy(), 4)])
    st = eng.stats()
    assert st["preempted"] == 0, st
    assert st["evicted_pages"] >= 1, st
    for r in ref:
        assert np.array_equal(res[r], ref[r]), r


def test_preempted_request_resumes_through_cache():
    """Preemption still works under the cache and the resumed request's
    outputs stay bit-identical to the dense oracle (its cached prompt
    pages may or may not survive eviction in between)."""
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (3, 10), 0,
                                 cfg.vocab)
    dense = np.asarray(E.generate(params, cfg, prompts, 12, max_len=32))
    eng = _engine(params, cfg, max_seqs=3, num_pages=10, prefill_chunk=16)
    res = eng.run([(np.asarray(prompts[i]), 12) for i in range(3)])
    assert eng.counters["preempted"] >= 1
    for i in range(3):
        assert np.array_equal(res[i], dense[i]), i


# ==========================================================================
# knobs, alignment, observability
# ==========================================================================
def test_prefill_chunk_aligns_to_page_size():
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert _engine(params, cfg, page_size=4, prefill_chunk=6).chunk == 4
    assert _engine(params, cfg, page_size=4, prefill_chunk=9).chunk == 8
    assert _engine(params, cfg, page_size=4, prefill_chunk=2).chunk == 4
    assert _engine(params, cfg, page_size=4, prefill_chunk=8).chunk == 8


def test_misaligned_chunk_request_still_matches_cold():
    """A prefill_chunk that is not a page multiple is aligned down, and
    warm runs over multi-chunk prompts stay bit-identical."""
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _shared_prefix_reqs(cfg.vocab, prefix_len=12, suffix_len=5,
                               seed=4)
    cold = _engine(params, cfg, prefix_cache=False, prefill_chunk=7,
                   table_width=8)
    ref = cold.run([(p.copy(), n) for p, n in reqs])
    eng = _engine(params, cfg, prefill_chunk=7, table_width=8)
    eng.run([(p.copy(), n) for p, n in reqs])
    res = eng.run([(p.copy(), n) for p, n in reqs])
    assert eng.stats()["prefix_hit_tokens"] > 0
    for k in range(len(reqs)):
        assert np.array_equal(res[k + len(reqs)], ref[k]), k


def test_stats_surface_and_reset():
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(params, cfg)
    eng.run([(np.arange(6, dtype=np.int32), 3)])
    st = eng.stats()
    for key in ("admitted", "completed", "preempted", "prefix_hits",
                "prefix_misses", "prefix_hit_tokens", "evicted_pages",
                "cow_copies", "deduped_pages", "gather_fallbacks",
                "dense_moe_fallbacks", "free_pages", "cached_pages"):
        assert key in st, key
    assert st["admitted"] == 1 and st["completed"] == 1
    eng.reset_stats()
    st = eng.stats()
    assert st["admitted"] == 0 and st["gather_fallbacks"] == 0


def test_prefix_cache_off_keeps_legacy_behavior():
    cfg = _cfg(P16_2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(params, cfg, prefix_cache=False)
    prompt = np.arange(8, dtype=np.int32)
    eng.run([(prompt.copy(), 4)])
    eng.run([(prompt.copy(), 4)])
    st = eng.stats()
    assert st["prefix_hits"] == 0 and st["cached_pages"] == 0
    assert st["cow_copies"] == 0 and st["deduped_pages"] == 0
    assert len(eng.free_pages) == eng.num_pages - 1


# ==========================================================================
# the acceptance row: 4-device DP warm/cold parity, subprocess
# ==========================================================================
_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.core.types import P16_2
    from repro.models.transformer import ModelConfig, init_params
    from repro.quant.policy import PositPolicy
    from repro.serving import engine as E
    from repro.launch.mesh import make_serving_mesh

    cfg = ModelConfig(name="tst-px4", n_layers=2, d_model=32, n_heads=4,
                      n_kv=2, d_ff=64, vocab=50,
                      policy=PositPolicy(kv_cache=P16_2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    reqs = [(np.concatenate([prefix,
                             rng.integers(0, cfg.vocab, 4).astype(np.int32)]),
             6) for _ in range(8)]

    ref = E.PagedServingEngine(params, cfg, max_seqs=8, page_size=4,
                               table_width=8, prefill_chunk=8,
                               prefix_cache=False)
    res_ref = ref.run([(p.copy(), n) for p, n in reqs])

    mesh = make_serving_mesh(4, 1)
    eng = E.PagedServingEngine(params, cfg, max_seqs=8, page_size=4,
                               table_width=8, prefill_chunk=8, mesh=mesh)
    cold = eng.run([(p.copy(), n) for p, n in reqs])
    for r in res_ref:
        assert np.array_equal(cold[r], res_ref[r]), ("cold", r)

    warm = eng.run([(p.copy(), n) for p, n in reqs])
    st = eng.stats()
    assert st["prefix_hit_tokens"] > 0, st
    for k in range(len(reqs)):
        assert np.array_equal(warm[k + len(reqs)], res_ref[k]), ("warm", k)

    # shard-local dedup: every table entry stays inside its shard's
    # sub-pool, so DP admission/paging is bitwise shard-independent
    for i, slot in enumerate(eng.slots):
        assert slot is None
    print("PREFIX-DP-OK")
""")


def test_prefix_cache_dp_sharded_warm_cold_bit_exact_4dev():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "PREFIX-DP-OK" in out.stdout
