"""A configuration brings its own model family by new files alone.

In a copy of the benchmark, new files add a family (probe_family.py as
bench/families/probe.py: the dense family with one more compared number
and one more kernel class), its configuration, and a metric file that
reads the new class; BENCHMARK.json gains the cell, its configuration and
the metric as new entries, and the cell joins the end-to-end metrics'
lists.  The cell runs end to end at its rehearsal sizes, with and
without the trace, and no file the copy had under bench/ changed."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import lastline
from bench.tests.helpers import ROOT, env, last_object

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "probe.offline"
METRIC = {"name": "probe_roofline.offline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s", "workloads": [CELL]}
READER = '''"""Roofline share of the probe family's `probe` kernel class."""
from bench.kernels import roofline


def read(ctx):
    return roofline(ctx, "probe")
'''


def _files(root):
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark copied, the probe's files added, and the bytes of
    every file the copy had before."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root / "bench")

    def add(rel, data):
        path = root / "bench" / rel
        assert not path.exists(), rel
        path.write_text(data)
    with open(os.path.join(HERE, "probe_family.py")) as f:
        add("families/probe.py", f.read())
    with open(root / "bench" / "configs" / "smollm-360m.p16.json") as f:
        conf = json.load(f)
    conf.update(name="probe", family="probe")
    conf["smoke"]["check"]["probe_min_decode_steps"] = 1
    add("configs/probe.json", json.dumps(conf, indent=1))
    add(f"metrics/{METRIC['name']}.py", READER)
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append(dict(spec["configs"][0], name="probe",
                                file="bench/configs/probe.json"))
    spec["workloads"].append(dict(spec["workloads"][0], name=CELL,
                                  config="probe"))
    spec["per_layer"].append(METRIC)
    for mdef in spec["end_to_end"]:
        if "workloads" in mdef:
            mdef["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f, indent=1)
    return root, before, spec


@pytest.mark.parametrize("trace", [0, 1])
def test_probe_cell_runs_by_new_files_alone(copy, tmp_path, trace):
    root, before, spec = copy
    e = env(tmp_path)
    e["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2 ** 33 + 29), "--seconds", "2", "--trace", str(trace),
         "--smoke"], cwd=root, env=e, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    obj = last_object(p.stdout.strip().splitlines()[-1])
    group = spec["per_layer"] if trace else spec["end_to_end"]
    expected = {m["name"]: m["unit"] for m in group
                if CELL in m.get("workloads", [CELL])}
    assert lastline.validate(obj, traced=bool(trace), expected=expected,
                             required_metrics=[] if trace else
                             list(expected)) == []
    assert obj["correct"] is True, obj["checks"]
    assert list(obj["checks"])[:5] == [
        "max_logit_gap", "sampled_tokens", "posit_domain_misses",
        "kernel_fallbacks", "compiles_in_window"]
    assert obj["checks"]["probe_decode_steps"]["value"] >= 1
    if trace:
        assert obj["metrics"][METRIC["name"]]["value"] > 0
    # no file the copy had before the probe's was changed
    after = _files(root / "bench")
    assert all(after.get(k) == v for k, v in before.items())
