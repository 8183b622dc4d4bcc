"""Every cell of BENCHMARK.json, run end to end at its rehearsal sizes on
the CPU (kernels interpreted), with and without the trace: each last line
keeps the result format by the validator's own rules, and the comparison with
the reference holds."""
import json
import os

import pytest

from bench import lastline
from bench.tests.helpers import ROOT, last_object, run_bench

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _expected(cell, traced):
    group = SPEC["per_layer"] if traced else SPEC["end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_last_line(tmp_path, cell, trace):
    rc, line, err = run_bench(tmp_path, cell, seed=2 ** 33 + 17,
                              trace=trace)
    assert rc == 0, err[-3000:]
    obj = last_object(line)
    expected = _expected(cell, bool(trace))
    required = [] if trace else list(expected)
    assert lastline.validate(obj, traced=bool(trace), expected=expected,
                             required_metrics=required) == []
    assert obj["correct"] is True, obj["checks"]
    assert list(obj)[-1] == "checks"


def test_no_result_without_the_program(tmp_path):
    """In a directory with only BENCHMARK.json and bench/, the run fails
    and prints no result."""
    import shutil
    import subprocess
    import sys
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_no_result_off_a_tpu(tmp_path):
    """Without --smoke the run insists on a TPU."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
