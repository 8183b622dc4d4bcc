"""The result line: built, checked against the format the benchmark
promises, and only then printed as the last line of standard output.

A line that breaks the format is never printed: the run prints why on
standard error and exits with code 3.
"""
from __future__ import annotations

import json
import math
import sys

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_KEYS = ("busy_s", "window_s")


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate(obj, *, traced: bool, expected: dict | None = None,
             required_metrics=()) -> list[str]:
    """Reasons `obj` breaks the format (empty when it keeps it).

    expected: {metric name: unit} the run may report; required_metrics:
    names it must report."""
    errs = []
    if not isinstance(obj, dict):
        return [f"the result is a {type(obj).__name__}, not an object"]
    for k in REQUIRED:
        if k not in obj:
            errs.append(f"key {k!r} is missing")
    if errs:
        return errs
    if not isinstance(obj["correct"], bool):
        errs.append("correct is not true or false")
    att, fail = obj["attempted"], obj["failed"]
    if type(att) is not int or type(fail) is not int:
        errs.append(f"attempted ({att!r}) and failed ({fail!r}) are not "
                    "whole numbers")
    elif att < 1:
        errs.append(f"attempted is {att}, not at least 1")
    elif not 0 <= fail <= att:
        errs.append(f"failed is {fail}, not from 0 to attempted ({att})")
    met = obj["metrics"]
    if not isinstance(met, dict) or not met:
        errs.append("metrics is not a non-empty object")
    else:
        for name, m in met.items():
            if (not isinstance(m, dict) or set(m) != {"value", "unit"}
                    or not _number(m["value"])
                    or not isinstance(m["unit"], str)):
                errs.append(f"metric {name!r} is not {{value, unit}} with a "
                            f"finite number: {m!r}")
            elif expected is not None and expected.get(name) != m["unit"]:
                errs.append(f"metric {name!r} is not one of this run's, or "
                            f"its unit {m['unit']!r} is not "
                            f"{expected.get(name)!r}")
        for name in required_metrics:
            if name not in met:
                errs.append(f"metric {name!r} is missing")
    dev = obj["device"]
    if not isinstance(dev, dict):
        errs.append("device is not an object")
    else:
        for k in DEVICE_KEYS + (TRACE_KEYS if traced else ()):
            if k not in dev:
                errs.append(f"device.{k} is missing")
        if "count" in dev and (type(dev["count"]) is not int
                               or dev["count"] < 1):
            errs.append(f"device.count is {dev['count']!r}")
        if "memory_peak_bytes" in dev and (
                type(dev["memory_peak_bytes"]) is not int
                or dev["memory_peak_bytes"] < 0):
            errs.append("device.memory_peak_bytes is not a whole number")
        if traced and all(k in dev for k in TRACE_KEYS):
            b, w = dev["busy_s"], dev["window_s"]
            if not (_number(b) and _number(w) and 0 < b <= w):
                errs.append(f"device busy_s {b!r} is not above 0 and at "
                            f"most window_s {w!r}")
    bd = obj.get("breakdown")
    if bd is not None:
        if not traced:
            errs.append("breakdown in an untraced run")
        for k in ("device_ops", "idle_gaps"):
            rows = bd.get(k, []) if isinstance(bd, dict) else None
            if (not isinstance(rows, list) or len(rows) > 10
                    or not all(isinstance(r, list) and len(r) == 2
                               and isinstance(r[0], str) and _number(r[1])
                               for r in rows)):
                errs.append(f"breakdown.{k} is not at most 10 "
                            "[name, seconds] pairs")
    try:
        json.dumps(obj, allow_nan=False)
    except (TypeError, ValueError) as e:
        errs.append(f"not JSON: {e}")
    return errs


def emit(obj: dict, *, traced: bool, expected: dict,
         required_metrics=()) -> None:
    """Print the checks on standard error, then `obj` as the last line of
    standard output, or exit 3 without it when it breaks the format."""
    for name, c in obj.get("checks", {}).items():
        print(f"bench check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    errs = validate(obj, traced=traced, expected=expected,
                    required_metrics=required_metrics)
    if errs:
        print("bench: the result line breaks the format and is not "
              "printed:\n  " + "\n  ".join(errs), file=sys.stderr, flush=True)
        sys.stdout.flush()
        sys.exit(3)
    sys.stdout.flush()
    print(json.dumps(obj, allow_nan=False), flush=True)
