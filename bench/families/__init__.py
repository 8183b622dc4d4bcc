"""Model families: the one seam through which a configuration's
architecture reaches the harness.

A configuration file names its family with the key "family" ("dense" when
it is missing), and bench/families/<family>.py is the module loaded for
it.  A new architecture is a new family module, a new configuration file
and new metric files: no file of the harness changes.  A family module
gives:

  stated(m), got(cfg)     what the configuration file's model block `m`
                          states of the program's ModelConfig, and what
                          the program's `cfg` says, key by key
                          (bench/program.py `model_config` compares them)
  leaf_specs(m), make_flat(key, m), nest(flat)
                          the weights' layout, drawn from the seed, and
                          the program's tree of them (`check_layout` holds
                          it against the program's parameter tree)
  serving_params(seed, m, cfg)
                          the weights as the program serves them, made on
                          the device from the seed
  teacher_forced(m, seed, seqs, first, length, collected=None)
  control_gaps(m, seed, seqs, first, length)
                          the plain reference and its control, as in
                          bench/reference.py; `collected` is what
                          `collect` took from the run
  collect(engine, sample) what the comparison and the work counts take from
                          the live engine, after the window closes and
                          before the program's state is freed; `sample`
                          is the sampled request ids
  extra_checks(m, check, collected)
                          {name: (value, limit, "max" or "min")}: numbers
                          compared beside the common five; `check` is the
                          configuration's check block
  step_work(m, rows, records)
                          the required work of each of the window's steps
                          ([{kernel class: bench.work.Work}]), from its
                          rows (bench/serve.py `Rows`) and `records`, what
                          `collect` took (the window's steps are the last
                          the engine ran)
  KERNELS                 {kernel class: trace-op name patterns}, which
                          bench/kernels.py `roofline` reads

Every hook runs before the first timed step or after the window closes.
"""
import importlib
import re


def load(conf: dict):
    """The family module of configuration `conf`."""
    name = conf.get("family", "dense")
    if not isinstance(name, str) or not re.fullmatch(r"[a-z][a-z0-9_]*",
                                                     name):
        raise SystemExit(f"bench: family {name!r} is not a module name")
    return importlib.import_module(f"bench.families.{name}")
