"""The result line's validator refuses every malformed line, including the
kinds of line a run that lost count of its requests could print."""
import copy

import pytest

from bench import lastline

GOOD = {"correct": True, "attempted": 400, "failed": 0,
        "metrics": {"serve_tok_s": {"value": 2100.5, "unit": "tokens/s"},
                    "setup_s": {"value": 41.2, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 13958643712},
        "checks": {"max_logit_gap": {"value": 0.0, "limit": 0.01}}}
UNITS = {"serve_tok_s": "tokens/s", "setup_s": "s"}


def _bad(mutate, traced=False):
    obj = copy.deepcopy(GOOD)
    mutate(obj)
    return lastline.validate(obj, traced=traced, expected=UNITS)


def test_good_line_passes():
    assert lastline.validate(GOOD, traced=False, expected=UNITS,
                             required_metrics=list(UNITS)) == []


@pytest.mark.parametrize("mutate", [
    lambda o: o.pop("attempted"),
    lambda o: o.update(attempted=400.0),
    lambda o: o.update(attempted=0),
    lambda o: o.update(attempted=True),
    lambda o: o.update(failed=-1),
    lambda o: o.update(failed=401),
    lambda o: o.update(failed=1.0),
    lambda o: o.pop("correct"),
    lambda o: o.update(correct="true"),
    lambda o: o.pop("metrics"),
    lambda o: o["metrics"]["serve_tok_s"].update(value=float("nan")),
    lambda o: o["metrics"]["serve_tok_s"].pop("unit"),
    lambda o: o["metrics"].update(mfu={"value": 1.0, "unit": "%"}),
    lambda o: o["device"].pop("memory_peak_bytes"),
    lambda o: o["device"].update(count=0),
], ids=["attempted-missing", "attempted-float", "attempted-zero",
        "attempted-bool", "failed-negative", "failed-above-attempted",
        "failed-float", "correct-missing", "correct-string",
        "metrics-missing", "metric-nan", "metric-no-unit",
        "metric-not-the-cells", "device-no-peak", "device-count-zero"])
def test_malformed_lines_are_refused(mutate):
    assert _bad(mutate)


def test_only_checks_line_is_refused():
    """A last line with nothing but the compared numbers."""
    assert lastline.validate({"checks": GOOD["checks"]}, traced=False)


def test_traced_line_needs_busy_and_window():
    assert _bad(lambda o: None, traced=True)
    ok = copy.deepcopy(GOOD)
    ok["device"].update(busy_s=1.5, window_s=10.0)
    assert lastline.validate(ok, traced=True, expected=UNITS) == []
    ok["device"].update(busy_s=11.0)
    assert lastline.validate(ok, traced=True, expected=UNITS)


def test_emit_exits_without_printing_a_bad_line(capsys):
    obj = copy.deepcopy(GOOD)
    obj["attempted"] = 0
    with pytest.raises(SystemExit) as e:
        lastline.emit(obj, traced=False, expected=UNITS)
    assert e.value.code == 3
    out = capsys.readouterr()
    assert out.out == ""
    assert "attempted is 0" in out.err
