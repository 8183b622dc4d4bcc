"""The dense family (bench/families/dense.py) gives what the harness gave
before the family seam: the same served weights and the same reference,
pinned here by numbers read from the harness before the move, at the
rehearsal sizes of smollm-360m.p16."""
import hashlib
import json
import os

import numpy as np
import pytest

from bench import families
from bench.tests.helpers import ROOT

SEED = 2 ** 33 + 17


def _conf():
    with open(os.path.join(ROOT, "bench", "configs",
                           "smollm-360m.p16.json")) as f:
        conf = json.load(f)
    return conf, dict(conf["model"], **conf["smoke"]["model"])


def test_a_configuration_without_family_is_dense():
    conf, _ = _conf()
    assert "family" not in conf
    assert families.load(conf).__name__ == "bench.families.dense"
    with pytest.raises(SystemExit):
        families.load({"family": "../dense"})


def test_served_weights_pinned():
    """sha256 over every leaf of the quantized tree (dtype, shape, bytes)."""
    import jax
    from bench import program
    conf, m = _conf()
    fam = families.load(conf)
    cfg = program.model_config(conf["arch"], m, fam)
    program.check_layout(cfg, m, fam)
    leaves = jax.tree_util.tree_leaves(fam.serving_params(SEED, m, cfg))
    h = hashlib.sha256()
    for x in leaves:
        a = np.asarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert len(leaves) == 11
    assert h.hexdigest() == ("da85f28120c135a20712f7359ba6c099"
                             "16b0fff55a769b0a6d02abf11914be3a")


def test_reference_gaps_pinned():
    """Per request, the widest and the summed gap of given tokens below
    the reference's best logit."""
    conf, m = _conf()
    fam = families.load(conf)
    rng = np.random.default_rng(SEED % 2 ** 32)
    seqs = [rng.integers(0, m["vocab"], n).astype(np.int32)
            for n in (40, 97, 256, 130)]
    first = [10, 30, 200, 64]
    gaps, _, bad = fam.teacher_forced(m, SEED, seqs, first, m["context"])
    assert bad == 0
    assert [float(g.max()) for g in gaps] == pytest.approx(
        [5.094352722167969, 5.429116725921631, 5.004403591156006,
         6.30072021484375], rel=1e-5)
    assert [float(g.astype(np.float64).sum()) for g in gaps] == \
        pytest.approx([88.74796783924103, 207.88543939590454,
                       175.01320374011993, 191.03824853897095], rel=1e-5)
