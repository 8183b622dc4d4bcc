"""Operations and bytes that the model's shapes require, per kernel call and
per step, and the chip's peaks to hold them against.

"Required" is what the model's arithmetic needs whatever implements it:
the rows of tokens that a step actually computes for a request (not
padding rows or empty slots), weights read once per call at the storage
width of their posit format, activations in f32, posit KV read once per
attention call.  A multiply-add counts as two operations.

A roofline share is the least time the chip could take for the calls,
each bound by the larger of ops / peak FLOP/s and bytes / peak bytes/s,
over the time the kernels took.
"""
from __future__ import annotations

import dataclasses
import json
import os

F32 = 4
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of `device_kind`; an unknown kind raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class Work:
    ops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, o: "Work"):
        self.ops += o.ops
        self.bytes += o.bytes
        return self

    def min_seconds(self, pk: dict) -> float:
        return max(self.ops / pk["flops_bf16"],
                   self.bytes / pk["hbm_bytes_per_s"])


def pw_gemm(m: int, k: int, n: int, wbytes: int) -> Work:
    """[m, k] f32 activations @ [k, n] posit weights -> [m, n] f32."""
    return Work(2.0 * m * k * n, k * n * wbytes + (m * k + m * n) * F32)


def flash_decode(ctx: int, H: int, KV: int, hd: int, kvbytes: int) -> Work:
    """One sequence's decode attention over `ctx` cached positions."""
    return Work(4.0 * ctx * H * hd,
                2 * ctx * KV * hd * kvbytes + 2 * H * hd * F32)


def flash_prefill(c: int, off: int, H: int, KV: int, hd: int,
                  kvbytes: int) -> Work:
    """One sequence's prefill chunk of `c` tokens after `off` cached ones
    (causal: token i sees off + i + 1 positions)."""
    seen = c * off + c * (c + 1) / 2
    return Work(4.0 * seen * H * hd,
                2 * (off + c) * KV * hd * kvbytes + 2 * c * H * hd * F32)


def step_work(m: dict, slots: list[tuple[int, int]]) -> dict[str, Work]:
    """Required work of one serving step, by kernel class.

    slots: (new tokens, tokens cached before) of every sequence the step
    computes for.  Classes: gemm (posit GEMMs: projections, MLP,
    unembedding), attn (flash prefill/decode), model (the whole forward:
    what mfu counts)."""
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ff, L, V = m["d_ff"], m["n_layers"], m["vocab"]
    wb = m["weight_posit"][0] // 8
    kb = m["kv_posit"][0] // 8
    T = sum(n for n, _ in slots)
    out = {k: Work() for k in ("gemm", "attn", "model")}
    if T == 0:
        return out
    layer = {k: Work() for k in ("gemm", "attn")}
    for k, n in [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d),
                 (d, ff), (d, ff), (ff, d)]:
        layer["gemm"] += pw_gemm(T, k, n, wb)
    for n_new, off in slots:
        if n_new == 1:
            layer["attn"] += flash_decode(off + 1, H, KV, hd, kb)
        elif n_new > 1:
            layer["attn"] += flash_prefill(n_new, off, H, KV, hd, kb)
    for k in ("gemm", "attn"):
        out[k] = Work(L * layer[k].ops, L * layer[k].bytes)
    # the step returns one token per sequence: the unembedding computes the
    # last position of each
    out["gemm"] += pw_gemm(len([s for s in slots if s[0]]), d, V, wb)
    out["model"] = Work(out["gemm"].ops + out["attn"].ops, 0)
    return out
