"""The comparison that decides `correct` fails a run whose timed path is
broken underneath, once for each fault a serving cell can have, and
passes the control of a lower precision nowhere.  (Training's faults and
the exchange between chips do not arise in these one-chip serving cells.)"""
import numpy as np
import pytest

from bench.tests.helpers import last_object, run_bench

KV_STATE_UNCHANGED = """
import repro.serving.paged_kv as pk
pk.paged_append_kv = lambda cache, k, v: cache
"""

TOKEN_ALTERED = """
import numpy as np
from repro.serving.engine import PagedServingEngine
_orig = PagedServingEngine._run_step
def _run_step(self, tokens, num_new, participants):
    toks, bad = _orig(self, tokens, num_new, participants)
    if toks is not None:
        # one token in five of every request, at a place that moves
        toks = np.array(toks)
        for i in participants:
            if (self._step_idx + i) % 5 == 0:
                toks[i] = (toks[i] + 1) % self.cfg.vocab
    return toks, bad
PagedServingEngine._run_step = _run_step
"""


@pytest.mark.parametrize("patch", [KV_STATE_UNCHANGED, TOKEN_ALTERED],
                         ids=["kv-state-unchanged", "token-altered"])
def test_broken_path_is_not_correct(tmp_path, patch):
    rc, line, err = run_bench(tmp_path, "smollm-360m.p16.offline", seed=31,
                              patch=patch)
    assert rc == 0, err[-3000:]
    obj = last_object(line)
    assert obj["correct"] is False, obj["checks"]
    assert obj["failed"] >= 1


@pytest.mark.parametrize("seed", [101, 2 ** 32 + 5, 3_000_000_019])
def test_lower_precision_control_fails(seed):
    """The reference one precision lower (bfloat16 operands) put in the
    program's place, at the rehearsal widths: at every position of the
    same sequences it reads the gap of its own first token, and the widest
    gap passes the configuration's rehearsal limit."""
    import json
    import os
    from bench import families
    from bench.tests.helpers import ROOT
    with open(os.path.join(ROOT, "bench", "configs",
                           "smollm-360m.p16.json")) as f:
        conf = json.load(f)
    fam = families.load(conf)
    m = dict(conf["model"], **conf["smoke"]["model"])
    limit = conf["smoke"]["check"]["max_logit_gap"]
    rng = np.random.default_rng(seed % 2 ** 32)
    seqs = [rng.integers(0, m["vocab"], 200).astype(np.int32)
            for _ in range(4)]
    first = [20] * 4
    gaps, bad = fam.control_gaps(m, seed, seqs, first, m["context"])
    assert bad == 0
    worst = max(float(g.max()) for g in gaps)
    assert worst > limit, worst
    # and the reference at its own precision against itself reads 0
    tops = fam.teacher_forced(m, seed, seqs, first, m["context"])[1]
    again = fam.teacher_forced(m, seed, seqs, first, m["context"],
                               picks=tops)[0]
    assert max(float(g.max()) for g in again) == 0.0
