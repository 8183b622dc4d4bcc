"""Roofline-term extraction from AOT-compiled artifacts (EXPERIMENTS §Roofline).

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

cost_analysis() FLOPs/bytes are for the SPMD-partitioned per-device module.
Collective bytes are not in cost_analysis: we parse the optimized HLO text
and sum buffer sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops (shapes there are per-device), with
ring-algorithm byte factors.

Hardware constants come from PEAKS, keyed by `device_kind` as JAX reports
it; a kind missing from the table is an error, not a default.  The dry-run
compiles for emulated devices, so it names its target kind (TARGET_KIND);
these terms are *structural*, derived from the compiled module, not
wall-clock.
"""
from __future__ import annotations

import dataclasses
import re

# Per-chip peaks.  TPU v5e: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect
# over 4 links = 50 GB/s per link and direction).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
TARGET_KIND = "TPU v5 lite"     # the production mesh (launch/mesh.py)


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_OP_RE = re.compile(
    r"^(?P<res>[^=]*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<variant>-start|-done)?\(")

_TUPLE_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    by_op: dict
    total_bytes: float       # ring-factored, per device

    @property
    def raw_bytes(self) -> float:
        return sum(v["bytes"] for v in self.by_op.values())


# ring-algorithm traffic factors (large-group limit), per device
_FACTORS = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def parse_collectives(hlo_text: str) -> CollectiveStats:
    by_op: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        _, rhs = line.split(" = ", 1)
        m = _OP_RE.match(rhs.strip())
        if not m or m.group("variant") == "-done":
            continue            # async start/done pairs: count the start only
        op = m.group("op")
        # sum the result buffer shapes (tuple for async starts)
        nbytes = sum(_shape_bytes(d, s)
                     for d, s in _TUPLE_SHAPE_RE.findall(m.group("res"))
                     if d in _DTYPE_BYTES)
        rec = by_op.setdefault(op, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    total = sum(_FACTORS[op] * v["bytes"] for op, v in by_op.items())
    return CollectiveStats(by_op=by_op, total_bytes=total)


def roofline_terms(compiled, n_devices: int, pk: dict) -> dict:
    """Three roofline terms (seconds) + raw counters from a compiled exe,
    against the per-chip peaks `pk` (an entry of PEAKS)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    mem = compiled.memory_analysis()

    terms = {
        "flops_per_device": flops,
        "bytes_per_device": byts,
        "collective_bytes_per_device": coll.total_bytes,
        "collectives_by_op": coll.by_op,
        "t_compute_s": flops / pk["flops_bf16"],
        "t_memory_s": byts / pk["hbm_bw"],
        "t_collective_s": coll.total_bytes / pk["ici_bw"],
        "n_devices": n_devices,
    }
    terms["bottleneck"] = max(
        ("compute", terms["t_compute_s"]),
        ("memory", terms["t_memory_s"]),
        ("collective", terms["t_collective_s"]),
        key=lambda kv: kv[1])[0]
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            terms[f"mem_{attr}"] = getattr(mem, attr)
    return terms


def model_flops(cfg, shape, decode: bool) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N_active per token decode/prefill."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.seq_len * shape.global_batch
    return 2.0 * n_active * 1 * shape.global_batch     # one decode token
