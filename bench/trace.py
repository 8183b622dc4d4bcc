"""Reduction of a JAX profiler trace to device busy time, time per device
operation, and the idle gaps named by the benchmark's host span open then.

The device planes are `/device:<kind>:<n>`; their `XLA Ops` line holds
one event per operation that ran.  Busy time is the union of those
intervals, averaged over the chips that ran anything.  On the CPU backend,
where there is no device plane, the operations run on the `tf_XLA...`
threads of the host plane; the reduction reads those instead, so that a
rehearsal without a chip exercises the same code (its numbers are the
CPU's and are never reported as a device's).

The benchmark's host spans are `jax.profiler.TraceAnnotation`s whose names
start with `bench.`; they are on the host plane, on the same clock.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def _union(intervals):
    """Sorted, merged [(start, end)] of `intervals`."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _is_cpu_op_line(name: str) -> bool:
    return name.startswith("tf_XLA")


def _cpu_op(name: str) -> bool:
    return not name.startswith("ThreadpoolListener")


def op_name(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`: the device line
    names an op by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(events):
    """The events that contain no other event: a `while` (the layer scan)
    holds the ops of its body and is not itself an op's time."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, ev in enumerate(evs):
        if (i + 1 < len(evs) and evs[i + 1][1] < ev[2]
                and evs[i + 1][2] <= ev[2]):
            continue
        out.append(ev)
    return out


def read_events(path: str):
    """(device ops by chip {chip: [(name, start_ns, end_ns)]}, host spans
    [(name, start_ns, end_ns)]) from one .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev = collections.defaultdict(list)
    cpu_ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns
                    dev[plane.name].append((op_name(ev.name), s,
                                            s + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                cpu_line = _is_cpu_op_line(line.name)
                for ev in line.events:
                    s = ev.start_ns
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, s, s + ev.duration_ns))
                    elif (cpu_line and ev.duration_ns > 0
                          and _cpu_op(ev.name)):
                        cpu_ops.append((ev.name, s, s + ev.duration_ns))
    if not dev and cpu_ops:
        dev["/host:CPU"] = cpu_ops
    return dict(dev), spans


def reduce_events(dev: dict, spans: list, window: tuple[float, float]
                  | None = None, top: int = 10) -> dict:
    """Busy seconds (mean over chips), seconds per operation name (summed
    over chips; ops that contain others are left out), and the longest
    idle gaps of the first chip, each named by the innermost benchmark
    span open at the gap's middle.

    window (start_ns, end_ns): clip to it; default the span of all
    events."""
    if not dev:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "gaps": [],
                "chips": 0}
    all_ev = [e for evs in dev.values() for e in evs]
    if window is None:
        window = (min(e[1] for e in all_ev), max(e[2] for e in all_ev))
    w0, w1 = window
    busy, ops = [], collections.Counter()
    merged_first = None
    for chip in sorted(dev):
        iv = [(max(s, w0), min(e, w1)) for _, s, e in dev[chip]
              if e > w0 and s < w1]
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        if merged_first is None:
            merged_first = merged
        for name, s, e in leaves(dev[chip]):
            if e > w0 and s < w1:
                ops[name] += (min(e, w1) - max(s, w0)) * 1e-9
    gaps = []
    prev = w0
    for s, e in merged_first + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    named = []
    for g0, dur in gaps[:top]:
        mid = g0 + dur / 2
        i = bisect.bisect_right(starts, mid)
        name = "no bench span"
        best = None
        for n, s, e in spans[max(0, i - 64):i]:
            if s <= mid < e and (best is None or s >= best):
                name, best = n, s
        named.append((name, dur * 1e-9))
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (w1 - w0) * 1e-9, "ops": dict(ops),
            "gaps": named, "chips": len(dev)}


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def kernel_seconds(ops: dict, patterns) -> float | None:
    """Seconds of the operations whose name contains any of `patterns`
    (None when none ran, so a reader reports nothing)."""
    hit = [v for k, v in ops.items() if any(p in k for p in patterns)]
    return sum(hit) if hit else None


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[n, d] for n, d in red["gaps"][:top]]}
