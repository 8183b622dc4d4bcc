"""The paged engine's phases (PagedServingEngine._phase): each step() is a
profiler step span `engine.step` whose six child spans tile it in order,
on the profiler's clock; the phase clocks that stats() reports agree with
those spans; and the per-step row record (`step_rows`) is what the device
step computed."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.types import P16_2
from repro.models.transformer import ModelConfig, init_params
from repro.quant.policy import PositPolicy
from repro.serving import engine as E

CHILDREN = ["engine.schedule", "engine.page_in", "engine.batch",
            "engine.dispatch", "engine.fetch", "engine.commit"]


def _engine():
    cfg = ModelConfig(name="tst", n_layers=2, d_model=32, n_heads=4, n_kv=2,
                      d_ff=64, vocab=50, policy=PositPolicy(kv_cache=P16_2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return E.PagedServingEngine(params, cfg, max_seqs=2, page_size=4,
                                table_width=8, prefill_chunk=8)


def _requests(n, seed):
    """n prompts of 3-13 tokens (one or two prefill chunks), 3-5 new
    tokens each: more requests than slots, so steps mix admission,
    prefill and decode."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 50, int(rng.integers(3, 14))).astype(np.int32),
             3 + i % 3) for i in range(n)]


def _drain(eng, reqs):
    for prompt, max_new in reqs:
        eng.submit(prompt, max_new)
    while eng.waiting or eng.active:
        eng.step()


def _record_step_inputs(eng):
    """Wrap the engine's jitted step: [(num_new, seq_lens before) of every
    slot that computes] per call, read from the step's own arguments."""
    taken, inner = [], eng._step_fn

    def step_fn(p, tokens, pages, pt, sl, nn, *rest):
        taken.append([(int(n), int(s)) for n, s in
                      zip(np.asarray(nn), np.asarray(sl)) if n > 0])
        return inner(p, tokens, pages, pt, sl, nn, *rest)
    eng._step_fn = step_fn
    return taken


def _engine_spans(log_dir):
    """[(name, start_ns, end_ns, arguments)] of the engine's host spans in
    the one profile written under log_dir, by start."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_step_spans_tile_each_step_on_the_profiler_clock(tmp_path):
    eng = _engine()
    _drain(eng, _requests(2, seed=0))        # both step programs compiled
    eng.reset_stats()
    taken = _record_step_inputs(eng)
    reqs = _requests(6, seed=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the engine's own spans only
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _drain(eng, reqs)
    st = eng.stats()
    spans = _engine_spans(str(tmp_path))

    steps = [s for s in spans if s[0] == "engine.step"]
    ran = [s for s in steps if "kind" in s[3]]
    assert len(ran) == st["prefill_steps"] + st["decode_steps"] == len(taken)
    assert [s[3]["kind"] for s in ran].count("prefill") == st["prefill_steps"]
    assert {s[3]["kind"] for s in ran} == {"prefill", "decode"}
    nums = [s[3]["step_num"] for s in ran]
    assert nums == list(range(nums[0], nums[0] + len(ran)))
    kids = [s for s in spans if s[0] in CHILDREN]
    held = tiled = 0
    for _, s0, e0, args in steps:
        inside = [k for k in kids if s0 <= k[1] and k[2] <= e0]
        held += len(inside)
        if "kind" not in args:
            # nothing to compute: retired, then no slot had work
            assert [k[0] for k in inside] in (CHILDREN[:1], CHILDREN[:2])
            continue
        assert [k[0] for k in inside] == CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
        tiled += sum(k[2] - k[1] for k in inside)
    assert held == len(kids)                 # no phase outside a step
    # summed over the steps: one stall of a loaded host between two
    # phases must not decide a single step's share
    assert tiled >= 0.9 * sum(e - s for _, s, e, _ in ran)
    assert sum(s[0] == "engine.submit" for s in spans) == len(reqs)

    # the phase clocks read what the spans read
    for ph in E.PHASES:
        mine = [s for s in spans if s[0] == f"engine.{ph}"]
        span_ms = sum(e - s for _, s, e, _ in mine) * 1e-6
        assert st[f"phase_ms_{ph}"] == pytest.approx(
            span_ms, rel=0.05, abs=0.05 * len(mine)), ph
    # one step-latency sample per step that ran, its engine.step reading
    lat_ms = sorted(1e3 * x for x in eng._step_lat_s)
    assert lat_ms == pytest.approx(
        sorted((e - s) * 1e-6 for _, s, e, _ in ran), rel=0.05, abs=0.05)

    # the row record is what each device step computed
    assert [list(rows) for _, rows in eng.step_rows] == taken
    assert [k for k, _ in eng.step_rows] == [s[3]["kind"] for s in ran]
    assert [s[3]["seqs"] for s in ran] == [len(r) for r in taken]


def test_phase_clocks_without_a_profiler_and_reset():
    eng = _engine()
    taken = _record_step_inputs(eng)
    _drain(eng, _requests(5, seed=2))
    st = eng.stats()
    kids_ms = sum(st[f"phase_ms_{c.split('.')[1]}"] for c in CHILDREN)
    assert 0.9 * st["phase_ms_step"] <= kids_ms <= st["phase_ms_step"]
    assert st["phase_ms_submit"] > 0
    assert len(eng._step_lat_s) == len(eng.step_rows) == len(taken)
    assert [list(rows) for _, rows in eng.step_rows] == taken
    assert st["step_latency_p99_ms"] >= st["step_latency_p50_ms"] > 0
    # decode took the jnp path here: no decode kernel ran
    assert st["decode_kv_blocks_run"] == st["decode_kv_blocks_grid"] == 0
    eng.reset_stats()
    st = eng.stats()
    assert all(st[f"phase_ms_{ph}"] == 0.0 for ph in E.PHASES)
    assert not eng.step_rows and st["step_latency_p50_ms"] == 0.0


def test_decode_kv_block_counters(monkeypatch):
    """stats()' decode_kv_blocks_run counts the blocks of pages the decode
    kernel ran: for each sequence of each decode step, the blocks of
    decode_pages_per_block pages its length (after the step's token)
    reaches, over every layer; decode_kv_blocks_grid counts the blocks of
    a grid over every slot's whole table."""
    from repro.kernels.flash_attention import decode_pages_per_block
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    cfg = ModelConfig(name="tst", n_layers=2, d_model=32, n_heads=4, n_kv=2,
                      d_ff=64, vocab=50, policy=PositPolicy(kv_cache=P16_2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    page, width, slots = 16, 24, 3
    eng = E.PagedServingEngine(params, cfg, max_seqs=slots, page_size=page,
                               table_width=width, prefill_chunk=64,
                               bucket_pages=False)
    rng = np.random.default_rng(4)
    # lengths that stay inside the first 128-token block, cross into the
    # second one during decode, and start past it
    _drain(eng, [(rng.integers(0, 50, n).astype(np.int32), 6)
                 for n in (60, 126, 200)])
    st = eng.stats()
    block = page * decode_pages_per_block(page, width)
    decode = [rows for kind, rows in eng.step_rows if kind == "decode"]
    assert len(decode) >= 4
    assert st["decode_kv_blocks_run"] == cfg.n_layers * sum(
        -(-(cached + new) // block) for rows in decode
        for new, cached in rows)
    assert st["decode_kv_blocks_grid"] == (
        cfg.n_layers * len(decode) * slots * -(-width // (block // page)))
    assert 0 < st["decode_kv_blocks_run"] < st["decode_kv_blocks_grid"]
    eng.reset_stats()
    assert eng.stats()["decode_kv_blocks_run"] == 0
