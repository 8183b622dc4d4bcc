"""Everything the benchmark takes from the program under test, in one place:
its model configuration, its post-training quantization, its serving
engine and its kernel-fallback counters.  The reference
and the yardstick import none of this.
"""
from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro import configs  # noqa: E402  (fails outside a checkout)
from repro.core.types import PositConfig  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402,F401 (re-exported)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402,F401
from repro.models.transformer import init_params  # noqa: E402
from repro.quant.policy import PositPolicy  # noqa: E402
from repro.quant.ptq import quantize_for_serving  # noqa: E402,F401 (re-exported)
from repro.serving.engine import PagedServingEngine  # noqa: E402


def model_config(arch: str, m: dict, fam):
    """The program's ModelConfig for config-file model block `m`, built
    from the program's own registry and checked key by key against what
    family `fam` says `m` states."""
    wn, wes = m["weight_posit"]
    kn, kes = m["kv_posit"]
    policy = PositPolicy(weights=PositConfig(wn, wes),
                         kv_cache=PositConfig(kn, kes))
    cfg = configs.get_config(arch, policy=policy)
    over = dict(n_layers=m["n_layers"], d_model=m["d_model"],
                n_heads=m["n_heads"], n_kv=m["n_kv_heads"],
                head_dim=m["head_dim"], d_ff=m["d_ff"], vocab=m["vocab"])
    cfg = dataclasses.replace(cfg, **over)
    stated, got = fam.stated(m), fam.got(cfg)
    bad = {k: (stated[k], got[k]) for k in stated if stated[k] != got[k]}
    if bad:
        raise SystemExit(f"bench: the program's {arch} config departs from "
                         f"the configuration file: {bad}")
    return cfg


def check_layout(cfg, m: dict, fam):
    """Family `fam`'s weight maker must give the program's parameter
    tree."""
    want = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: fam.nest(fam.make_flat(k, m)),
                         jax.random.PRNGKey(0))
    sw = jax.tree_util.tree_structure(want)
    sg = jax.tree_util.tree_structure(got)
    shapes_w = [x.shape for x in jax.tree_util.tree_leaves(want)]
    shapes_g = [x.shape for x in jax.tree_util.tree_leaves(got)]
    if sw != sg or shapes_w != shapes_g:
        raise SystemExit(f"bench: weight layout differs from the program's "
                         f"parameter tree:\n{sw}\n{sg}")


def make_engine(params, cfg, eng: dict, seed: int):
    return PagedServingEngine(
        params, cfg, max_seqs=eng["max_seqs"], page_size=eng["page_size"],
        table_width=eng["table_width"], num_pages=eng.get("num_pages"),
        prefill_chunk=eng["prefill_chunk"], bucket_pages=eng["bucket_pages"],
        seed=seed)


def fallback_count() -> int:
    """Sum of the program's kernel-fallback counters (they count traces)."""
    from repro.kernels.ops import BWD_FALLBACKS, RECURRENT_FALLBACKS
    from repro.models.moe import DENSE_MOE_FALLBACKS
    from repro.serving.paged_kv import GATHER_FALLBACKS
    return sum(sum(c.values()) for c in (GATHER_FALLBACKS,
                                         DENSE_MOE_FALLBACKS,
                                         RECURRENT_FALLBACKS, BWD_FALLBACKS))


def free_device_memory():
    """Drop every live device buffer the process still holds."""
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()

