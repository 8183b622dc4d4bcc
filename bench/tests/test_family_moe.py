"""The sparse-expert family (bench/families/moe.py) at the rehearsal sizes of
olmoe-1b-7b.p16: the cell runs end to end and is correct; a program that
routes to another expert than the top-k, or the reference one precision
lower in the program's place, is not; the weights made layer by layer are
the whole tree's; and the expert work is counted from the routing
record."""
import json
import os

import numpy as np
import pytest

from bench import families
from bench.tests.helpers import ROOT, last_object, run_bench

CELL = "olmoe-1b-7b.p16.offline"
SEED = 2 ** 33 + 41
COMMON = ["max_logit_gap", "sampled_tokens", "posit_domain_misses",
          "kernel_fallbacks", "compiles_in_window"]

# the program routes each token to its (k+1)-th expert in place of its
# k-th, and records what it did: the logits follow the routing it used,
# and only the routing comparison can see the fault
SECOND_CHOICE = """
import repro.models.moe as MOE
_orig = MOE._route
def _route(xt, p, *, n_experts, top_k, cap, policy, norm_topk=True):
    out = _orig(xt, p, n_experts=n_experts, top_k=top_k + 1, cap=cap,
                policy=policy, norm_topk=norm_topk)
    pick = list(range(top_k - 1)) + [top_k]
    return (out[0],) + tuple(None if a is None else a[:, :, pick]
                             for a in out[1:])
MOE._route = _route
"""


# the program one precision lower, as the chip's default matmul precision
# runs it: every GEMM's activations and decoded posit operands (weights,
# K/V) rounded to bfloat16, the products summed in f32
BF16_PROGRAM = """
import jax.numpy as jnp
import repro.kernels.ops as K
from repro.kernels import flash_attention, grouped_gemm, posit_gemm
def _bf(t):
    return t.astype(jnp.bfloat16).astype(t.dtype)
for _mod in (flash_attention, grouped_gemm, posit_gemm):
    _mod.decode_to_f32 = (lambda w, cfg, _d=_mod.decode_to_f32:
                          _bf(_d(w, cfg)))
_pw, _gm = K.pw_matmul, K.grouped_matmul
K.pw_matmul = lambda x, w, *a, **k: _pw(_bf(x), w, *a, **k)
K.grouped_matmul = lambda x, w, off, **k: _gm(_bf(x), w, off, **k)
"""


def _conf():
    with open(os.path.join(ROOT, "bench", "configs",
                           "olmoe-1b-7b.p16.json")) as f:
        conf = json.load(f)
    return conf, dict(conf["model"], **conf["smoke"]["model"])


@pytest.mark.parametrize("trace", [0, 1])
def test_smoke_cell_is_correct(tmp_path, trace):
    rc, line, err = run_bench(tmp_path, CELL, seed=SEED, trace=trace)
    assert rc == 0, err[-3000:]
    obj = last_object(line)
    assert obj["correct"] is True, obj["checks"]
    assert list(obj["checks"]) == COMMON + ["route_gap", "route_swaps"]
    if trace:
        # the whole step's share of the peak, over the active parameters
        # (bench/metrics/mfu.serve.py on this family's step_work)
        assert obj["metrics"]["mfu.serve"]["value"] > 0
        assert obj["metrics"]["step_ms.offline"]["value"] > 0


def test_routing_swap_is_not_correct(tmp_path):
    rc, line, err = run_bench(tmp_path, CELL, seed=SEED,
                              patch=SECOND_CHOICE)
    assert rc == 0, err[-3000:]
    obj = last_object(line)
    c = obj["checks"]
    assert obj["correct"] is False, c
    assert c["route_gap"]["value"] > c["route_gap"]["limit"], c
    assert c["route_swaps"]["value"] >= 1, c
    # the reference followed the program's routing: the logits agree
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"], c


def test_bf16_operand_program_is_not_correct(tmp_path):
    """The control through the harness's own comparison: the program with
    bfloat16 GEMM operands is served and checked as any run is, and fails
    a compared number."""
    rc, line, err = run_bench(tmp_path, CELL, seed=SEED, patch=BF16_PROGRAM)
    assert rc == 0, err[-3000:]
    obj = last_object(line)
    c = obj["checks"]
    assert obj["correct"] is False, c
    assert (c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
            or c["route_gap"]["value"] > c["route_gap"]["limit"]), c
    for name in ("posit_domain_misses", "kernel_fallbacks",
                 "compiles_in_window"):
        assert c[name]["value"] <= c[name]["limit"], c


@pytest.mark.parametrize("seed", [101, 2 ** 32 + 5])
def test_lower_precision_control_fails(seed):
    """The reference one precision lower (bfloat16 operands, its own
    routing and tokens) in the program's place fails the rehearsal limits;
    the reference against itself reads 0 on both."""
    conf, m = _conf()
    fam = families.load(conf)
    check = conf["smoke"]["check"]
    rng = np.random.default_rng(seed % 2 ** 32)
    seqs = [rng.integers(0, m["vocab"], n).astype(np.int32)
            for n in (120, 200, 77)]
    first = [20, 20, 20]
    gaps, bad, route = fam.control(m, seed, seqs, first, m["context"])
    assert bad == 0
    worst = max(float(g.max()) for g in gaps)
    assert worst > check["max_logit_gap"] or \
        route["route_gap"] > check["route_gap"], (worst, route)
    # the reference given its own routing and tokens
    _, tops, _, _, own = fam._forward(m, seed, seqs, first, m["context"],
                                      "highest", None, None)
    collected = {"routes": own}
    again = fam.teacher_forced(m, seed, seqs, first, m["context"],
                               collected=collected, picks=tops)[0]
    assert max(float(g.max()) for g in again) == 0.0
    assert collected["route"]["route_gap"] == 0.0
    assert collected["route"]["route_swaps"] == 0


def test_weights_made_layer_by_layer_are_the_whole_trees():
    """serving_params, one layer at a time, equals the program's PTQ of the
    whole f32 tree, leaf for leaf."""
    import jax
    from bench import program
    from bench import weights as W
    conf, m = _conf()
    fam = families.load(conf)
    cfg = program.model_config(conf["arch"], m, fam)
    program.check_layout(cfg, m, fam)
    got = fam.serving_params(SEED, m, cfg)
    want = jax.jit(lambda key: program.quantize_for_serving(
        fam.nest(fam.make_flat(key, m)), cfg.policy.weights))(
            W.seed_key(SEED))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_expert_work_counted_from_the_record():
    """Per layer-step, each expert that received rows reads its three
    posit matrices once and computes 3 x 2 x d x ff operations a row; the
    window's steps are the record's last."""
    conf, m = _conf()
    fam = families.load(conf)
    d, ff, L = m["d_model"], m["d_ff"], m["n_layers"]
    E, k = m["moe"]["n_experts"], m["moe"]["top_k"]
    # one decode step of 3 sequences: every (token, k) pair on experts 0-7
    load = np.zeros((L, E), np.int64)
    load[:, :k] = 3
    before = np.zeros((L, E), np.int64)
    rows = [[], [(1, 10), (1, 20), (1, 5)]]
    w = fam.step_work(m, rows, {"expert_rows": [before, load]})
    assert w[0]["moe"].ops == 0
    assert w[1]["moe"].ops == L * k * 3 * 3 * 2 * d * ff
    wbytes = L * k * 3 * d * ff * 2
    # three GEMMs of 3 rows, f32 in and out: d + ff values a row each
    acts = L * k * 3 * 3 * 4 * (d + ff)
    assert w[1]["moe"].bytes == wbytes + acts
    assert w[1]["model"].ops > w[1]["gemm"].ops + w[1]["attn"].ops + \
        w[1]["moe"].ops
    # a record that disagrees with the window's rows is refused
    with pytest.raises(SystemExit):
        fam.step_work(m, rows, {"expert_rows": [np.zeros_like(load)]})
