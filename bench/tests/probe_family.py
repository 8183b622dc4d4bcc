"""A family made only to show that one is taken by new files alone:
test_family_probe.py copies it into a copy of the benchmark as
bench/families/probe.py.  It is the dense family with one more compared
number, read from the live engine by `collect`, and one more kernel
class, `probe`, which a metric file of its own reads."""
from bench.families import dense

stated, got = dense.stated, dense.got
leaf_specs, make_flat, nest = dense.leaf_specs, dense.make_flat, dense.nest
serving_params = dense.serving_params
teacher_forced, control_gaps = dense.teacher_forced, dense.control_gaps

# every op, so that the class is found in a CPU trace too
KERNELS = dict(dense.KERNELS, probe=("",))


def collect(engine, sample):
    return {"decode_steps": int(engine.counters["decode_steps"]),
            "sampled": len(sample)}


def extra_checks(m, check, collected):
    return {"probe_decode_steps": (collected["decode_steps"],
                                   check["probe_min_decode_steps"], "min")}


def step_work(m, rows, records):
    assert records["sampled"] >= 1
    out = dense.step_work(m, rows, None)
    for w in out:
        w["probe"] = w["model"]
    return out
