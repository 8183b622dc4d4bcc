"""Running the benchmark's command at the rehearsal (smoke) sizes on the
CPU, with the Pallas kernels in interpret mode."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def env(tmp_path):
    e = dict(os.environ)
    e.update(JAX_PLATFORMS="cpu", REPRO_USE_PALLAS="1",
             REPRO_PALLAS_INTERPRET="1",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
             PYTHONPATH=ROOT)
    return e


def run_bench(tmp_path, workload, seed, trace=0, seconds=2.0, patch=""):
    """Run bench/run.py --smoke (after `patch`, Python run first in the
    same process); returns (exit code, last stdout line, stderr)."""
    args = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--smoke"]
    code = (f"import sys; sys.path.insert(0, {ROOT!r}); "
            f"sys.path.insert(0, {os.path.join(ROOT, 'src')!r})\n{patch}\n"
            f"from bench import run\nsys.exit(run.main({args!r}))")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=env(tmp_path), capture_output=True, text=True,
                       timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (lines[-1] if lines else ""), p.stderr


def last_object(line: str) -> dict:
    return json.loads(line)
