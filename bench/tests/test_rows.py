"""The serving harness's own account of each step's rows (bench/serve.py
`Rows`), which the rooflines and mfu.serve are computed from, against the
(new tokens, cached tokens) that the engine's step really took, read here
from the program at the rehearsal sizes."""
import json
import os

import numpy as np

from bench.serve import Rows
from bench.tests.helpers import ROOT


def _engine(seed):
    from bench import families, program
    with open(os.path.join(ROOT, "bench", "configs",
                           "smollm-360m.p16.json")) as f:
        conf = json.load(f)
    m = dict(conf["model"], **conf["smoke"]["model"])
    eng = dict(conf["engine"], **conf["smoke"]["engine"])
    eng.pop("num_pages")
    fam = families.load(conf)
    cfg = program.model_config(conf["arch"], m, fam)
    engine = program.make_engine(fam.serving_params(seed, m, cfg), cfg,
                                 eng, seed)
    taken = []
    inner = engine._step_fn

    def step_fn(p, tokens, pages, pt, sl, nn, *rest):
        nn, sl = np.asarray(nn), np.asarray(sl)
        taken.append([(int(n), int(s)) for n, s in zip(nn, sl) if n > 0])
        return inner(p, tokens, pages, pt, sl, nn, *rest)
    engine._step_fn = step_fn
    return engine, eng, m, taken


def _drive(engine, book, prompts):
    for i, p in enumerate(prompts):
        book.submit(engine.submit(p, 3 + i % 4), len(p))
    while engine.waiting or engine.active:
        before = book.counters(engine)
        book.after_step(engine, before, engine.step())


def test_rows_match_the_engine_steps():
    engine, eng, m, taken = _engine(5)
    rng = np.random.default_rng(5)
    # prompts of one to three chunks, more requests than slots
    prompts = [rng.integers(0, m["vocab"], n).astype(np.int32)
               for n in (9, 70, 33, 5, 40, 64, 17, 90)]
    book = Rows(eng["prefill_chunk"])
    _drive(engine, book, prompts)
    assert book.sound
    steps = [r for r in book.steps if r]
    assert [sorted(r) for r in steps] == [sorted(r) for r in taken]
    assert any(n > 1 for r in steps for n, _ in r)
    assert any(n == 1 for r in steps for n, _ in r)


def test_a_prefix_hit_leaves_no_rows():
    engine, eng, m, _ = _engine(6)
    p = np.random.default_rng(6).integers(0, m["vocab"], 48)
    book = Rows(eng["prefill_chunk"])
    _drive(engine, book, [p.astype(np.int32)])
    _drive(engine, book, [p.astype(np.int32)])
    assert not book.sound
