"""Compile-only checks of the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at smollm-360m widths (d_model
960, 15 heads over 5 KV heads of 64, d_ff 2560, vocab 49152, 16-token
pages), and the grouped expert GEMM at olmoe-1b-7b's.  This catches what interpret mode cannot: Mosaic's tiling rules,
its supported casts and reshapes, and the scoped-VMEM budget.  The
topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import P8_2, P16_2
from repro.kernels import flash_attention as fa
from repro.kernels import grouped_gemm as gg
from repro.kernels import posit_codec as codec
from repro.kernels import posit_elementwise as ew
from repro.kernels import posit_gemm as gemm
from repro.kernels import recurrent_scan as rs

D, H, N_KV, HD, D_FF, VOCAB, PAGE = 960, 15, 5, 64, 2560, 49152, 16
I16, F32, I32 = jnp.int16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to check
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _storage(cfg):
    return jnp.dtype(f"int{cfg.storage_bits}")


# (id, kernel, argument shapes, static keywords)
CASES = [
    *[(f"pw_gemm-{nm}-decode", gemm.pw_gemm,
       [((8, D), F32), ((D, D_FF), _storage(c))], {"cfg": c})
      for nm, c in (("p8", P8_2), ("p16", P16_2))],
    *[(f"pw_gemm-{nm}-unembed", gemm.pw_gemm,
       [((8, D), F32), ((VOCAB, D), _storage(c))],
       {"cfg": c, "transpose_b": True})
      for nm, c in (("p8", P8_2), ("p16", P16_2))],
    ("paged_flash_decode-p16", fa.paged_flash_decode,
     [((8, H, HD), F32), ((64, N_KV, PAGE, HD), I16),
      ((64, N_KV, PAGE, HD), I16), ((8, 33), I32), ((8,), I32)],
     {"cfg_kv": P16_2}),
    # the offline cell's decode: 64 sequences over a table of width 128
    # and a pool of 64 x 128 pages and the null page
    ("paged_flash_decode-p16-cell", fa.paged_flash_decode,
     [((64, H, HD), F32), ((8193, N_KV, PAGE, HD), I16),
      ((8193, N_KV, PAGE, HD), I16), ((64, 128), I32), ((64,), I32)],
     {"cfg_kv": P16_2}),
    ("paged_flash_prefill-p16", fa.paged_flash_prefill,
     [((8, H, 128, HD), F32), ((64, N_KV, PAGE, HD), I16),
      ((64, N_KV, PAGE, HD), I16), ((8, 33), I32), ((8,), I32),
      ((8,), I32)], {"cfg_kv": P16_2}),
    ("flash_prefill_fwd", fa.flash_prefill_contiguous,
     [((8, H, 512, HD), F32), ((8, N_KV, 512, HD), F32),
      ((8, N_KV, 512, HD), F32), ((8,), I32), ((8,), I32)],
     {"return_lse": True}),
    ("flash_prefill_bwd", fa.flash_prefill_bwd_contiguous,
     [((8, H, 512, HD), F32), ((8, N_KV, 512, HD), F32),
      ((8, N_KV, 512, HD), F32), ((8, H, 512, HD), F32),
      ((8, H, 512), F32), ((8, H, 512, HD), F32), ((8,), I32),
      ((8,), I32)], {}),
    ("encode_block-p16", codec.encode_block, [((D, D_FF), F32)],
     {"cfg": P16_2}),
    ("elementwise_add-p16", functools.partial(ew.elementwise, "add"),
     [((D, D_FF), I16), ((D, D_FF), I16)], {"cfg": P16_2}),
    ("posit_grouped_gemm-p16", gg.posit_grouped_gemm,
     [((2048, 1024), F32), ((64, 1024, 1024), I16), ((65,), I32)],
     {"cfg_b": P16_2}),
    # the olmoe-1b-7b.p16.offline cell (d_model 2048, 64 experts of 1024):
    # decode, 64 sequences x top-8 = 512 rows; prefill, 64 x 128 x 8
    *[(f"posit_grouped_gemm-p16-olmoe-{step}-{k}to{n}",
       gg.posit_grouped_gemm,
       [((rows, k), F32), ((64, k, n), I16), ((65,), I32)],
       {"cfg_b": P16_2})
      for step, rows in (("decode", 512), ("prefill", 65536))
      for k, n in ((2048, 1024), (1024, 2048))],
    # 13 tokens: two 8-row time tiles, the second padded and masked
    ("wkv_scan-p16", rs.wkv_scan_pallas,
     [((8, 40, 13, 64), F32)] * 4
     + [((40, 64), F32), ((8, 40, 64, 64), I16), ((8,), I32)],
     {"cfg_state": P16_2, "posit_state": True}),
    ("rglru_scan-p16", rs.rglru_scan_pallas,
     [((8, 13, 4096), F32)] * 2 + [((8, 4096), I16), ((8,), I32)],
     {"cfg_state": P16_2, "posit_state": True}),
]


@pytest.mark.parametrize("kernel,shapes,static",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, kernel, shapes, static):
    fn = lambda *a: kernel(*a, **static)                   # noqa: E731
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the op name the device trace gives each kernel: its pallas_call's `name`
OP_NAMES = {
    "pw_gemm-p8-decode": "posit_gemm", "pw_gemm-p16-decode": "posit_gemm",
    "pw_gemm-p8-unembed": "posit_gemm", "pw_gemm-p16-unembed": "posit_gemm",
    "paged_flash_decode-p16": "paged_flash_decode",
    "paged_flash_decode-p16-cell": "paged_flash_decode",
    "paged_flash_prefill-p16": "paged_flash_prefill",
    "flash_prefill_fwd": "flash_prefill_contiguous",
    "flash_prefill_bwd": "flash_prefill_bwd_contiguous",
    "encode_block-p16": "encode_block",
    "elementwise_add-p16": "elementwise",
    "posit_grouped_gemm-p16": "posit_grouped_gemm",
    **{f"posit_grouped_gemm-p16-olmoe-{step}-{k}to{n}": "posit_grouped_gemm"
       for step in ("decode", "prefill") for k, n in ((2048, 1024),
                                                      (1024, 2048))},
    "wkv_scan-p16": "wkv_scan_pallas",
    "rglru_scan-p16": "rglru_scan_pallas",
}


def _unjitted(kernel):
    """The kernel's Python body without its own jit."""
    if isinstance(kernel, functools.partial):
        return functools.partial(_unjitted(kernel.func), *kernel.args,
                                 **kernel.keywords)
    return getattr(kernel, "__wrapped__", kernel)


@pytest.mark.parametrize("case,kernel,shapes,static", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_op_keeps_its_name(one_chip, case, kernel, shapes, static):
    """Called from a jitted function of another name, each kernel's op
    keeps the name the device trace reads (a pallas_call without `name`
    takes its enclosing jit's), so renaming a wrapper renames no op."""
    def renamed(*a):
        return _unjitted(kernel)(*a, **static)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(renamed).lower(*args).compile().as_text()
    ops = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                     r"\"tpu_custom_call\"", text)
    assert ops
    for op in ops:
        assert re.fullmatch(rf"{OP_NAMES[case]}(\.\d+)?", op), op
