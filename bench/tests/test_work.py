"""bench/work.py and the dense family's step_work against counts made by hand
in this file, and against totals pinned before the family seam."""
import pytest

from bench import families, work

DENSE = families.load({})

SMOLLM = {"n_layers": 32, "d_model": 960, "n_heads": 15, "n_kv_heads": 5,
          "head_dim": 64, "d_ff": 2560, "vocab": 49152,
          "weight_posit": [16, 2], "kv_posit": [16, 2]}


def test_pw_gemm_decode_and_prefill():
    # decode: 64 rows through smollm's up projection 960 -> 2560
    w = work.pw_gemm(64, 960, 2560, 2)
    assert w.ops == 2 * 64 * 960 * 2560 == 314572800
    assert w.bytes == 960 * 2560 * 2 + 64 * 960 * 4 + 64 * 2560 * 4 \
        == 5816320
    # prefill: a 128-token chunk of 8 slots = 1024 rows
    w = work.pw_gemm(1024, 960, 2560, 2)
    assert w.ops == 5033164800
    assert w.bytes == 4915200 + 3932160 + 10485760


def test_flash_decode_and_prefill():
    # one smollm sequence decoding at context 1000
    w = work.flash_decode(1000, 15, 5, 64, 2)
    assert w.ops == 4 * 1000 * 15 * 64 == 3840000
    assert w.bytes == 2 * 1000 * 5 * 64 * 2 + 2 * 15 * 64 * 4 == 1287680
    # a 128-token chunk after 256 cached tokens: token i sees 256 + i + 1
    w = work.flash_prefill(128, 256, 15, 5, 64, 2)
    seen = sum(256 + i + 1 for i in range(128))
    assert seen == 41024
    assert w.ops == 4 * seen * 15 * 64
    assert w.bytes == 2 * 384 * 5 * 64 * 2 + 2 * 128 * 15 * 64 * 4


def test_step_work_smollm_decode_step():
    # two sequences decoding with 99 and 499 tokens cached
    w = DENSE.step_work(SMOLLM, [[(1, 99), (1, 499)]], None)[0]
    proj = 2 * 2 * (960 * 960 + 2 * 960 * 320 + 960 * 960)
    mlp = 2 * 2 * 3 * 960 * 2560
    unembed = 2 * 2 * 960 * 49152
    assert w["gemm"].ops == 32 * (proj + mlp) + unembed
    attn = 4 * (100 + 500) * 15 * 64
    assert w["attn"].ops == 32 * attn
    assert w["model"].ops == w["gemm"].ops + w["attn"].ops
    weights = 32 * 2 * (2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560)
    # every weight once per step, and each call's f32 rows in and out
    acts = 32 * 2 * 4 * (2 * 1920 + 2 * 1280 + 2 * 3520 + 3520)
    unembed_bytes = 2 * 960 * 49152 + 2 * 4 * (960 + 49152)
    assert w["gemm"].bytes == weights + acts + unembed_bytes


def test_dense_step_work_totals_pinned():
    """The dense family's work for fixed rows (prefill chunks, decode at
    several depths, an empty step), summed over the steps: the totals
    bench/work.py gave before the family seam."""
    steps = [[(128, 0), (128, 128), (37, 256)],
             [(1, 99), (1, 1023), (1, 2047)], [], [(64, 0), (1, 500)]]
    got = DENSE.step_work(SMOLLM, steps, None)
    assert len(got) == len(steps)
    tot = {k: (sum(w[k].ops for w in got), sum(w[k].bytes for w in got))
           for k in ("gemm", "attn", "model")}
    assert tot == {"gemm": (227876536320.0, 2955843584.0),
                   "attn": (5999493120.0, 269516800.0),
                   "model": (233876029440.0, 0.0)}


def test_peaks_known_and_unknown():
    pk = work.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
