"""The serving harness's own account of each step's rows (bench/serve.py
`Rows`) against the engine's per-step record,
`PagedServingEngine.step_rows`, at the rehearsal sizes: the record a later
harness can read in place of its own account."""
import numpy as np

from bench.serve import Rows
from bench.tests.test_rows import _drive, _engine


def test_rows_match_the_engine_step_record():
    engine, eng, m, _ = _engine(7)
    rng = np.random.default_rng(7)
    # prompts of one to three chunks, more requests than slots
    prompts = [rng.integers(0, m["vocab"], n).astype(np.int32)
               for n in (12, 66, 31, 7, 45, 80)]
    book = Rows(eng["prefill_chunk"])
    _drive(engine, book, prompts)
    assert book.sound
    record = list(engine.step_rows)
    assert [sorted(r) for r in book.steps if r] == [
        sorted(rows) for _, rows in record]
    kinds = [k for k, _ in record]
    st = engine.stats()
    assert kinds.count("prefill") == st["prefill_steps"] > 0
    assert kinds.count("decode") == st["decode_steps"] > 0
    assert all(n == 1 for k, rows in record if k == "decode"
               for n, _ in rows)
