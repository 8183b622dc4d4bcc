"""Pallas TPU kernel: blockwise (flash) attention with posit KV-cache decode
fused into the score/value matmuls.

Serving is memory-bound on KV-cache reads; storing KV as posit16/posit8
halves/quarters those bytes (paper C4/C6 applied to LMs — the central
serving win measured in EXPERIMENTS.md §Perf).  The decode (stage (i) of
the FPPU) happens on VMEM tiles right before the MXU, so HBM only ever sees
the narrow ints.

Standard online-softmax across KV blocks; supports causal masking with a
query-position offset (decode steps: q_len << kv_len).

Three fused entry points share the decode-before-the-MXU structure:
  * flash_attention            — contiguous KV, rectangular batch (training)
  * paged_flash_decode         — Sq == 1 over the paged pool (serving decode)
  * paged_flash_prefill /      — Sq >= 1 over the paged pool / a contiguous
    flash_prefill_contiguous     cache: the chunked-prefill + TTFT hot path.
                                 One kernel body, two BlockSpec wirings; the
                                 page table (paged) or the block index
                                 (contiguous) picks each KV tile, and
                                 causal/q_offset/window/softcap are masked
                                 in-kernel, so no `gather_kv` dense
                                 materialization exists on the TPU path for
                                 any Sq.

Every grid is tagged with `dimension_semantics`: batch and q-tile axes are
"parallel" (no cross-iteration state), the KV axis is "arbitrary" (it
carries the online-softmax running max/sum/acc), which lets Mosaic
parallelize across cores without breaking the accumulation order.  The
decode kernel has no KV grid axis: each sequence's grid step loops over
only its live blocks of pages, copying them from the pool itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.decode import decode_to_f32
from repro.core.types import PositConfig
from repro.kernels.tpu_params import compiler_params

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  cfg_kv, nkv, scale, causal, bq, bk, q_offset, kv_len):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0]
    v = v_ref[0]
    if cfg_kv is not None:
        k = decode_to_f32(k, cfg_kv)
        v = decode_to_f32(v, cfg_kv)
    else:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = q_offset + pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    kpos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < kv_len                             # mask KV padding
    if causal:
        valid = valid & (qpos >= kpos)
    s = jnp.where(valid, s, _NEG)

    m_prev = m_ref[...][:, :1]                        # (bq, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)                   # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
        p.sum(axis=1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(kv_idx == nkv - 1)
    def _done():
        l = l_ref[...][:, :1]
        o_ref[0] = acc_ref[...] / jnp.where(l == 0, 1.0, l)


def decode_pages_per_block(page: int, width: int) -> int:
    """Pages paged_flash_decode copies and computes at once: a block of
    about 128 tokens, never more pages than the table is wide."""
    return min(width, max(1, 128 // page))


def _live_blocks(seq_len, bt, window, maximum):
    """[first, end) of the bt-token blocks that hold a position the decode
    query attends, [seq_len - window, seq_len): empty at seq_len 0.
    `maximum` is np.maximum on the host and jnp.maximum in the kernel."""
    end = (seq_len + bt - 1) // bt
    first = 0 if window is None else maximum(seq_len - window, 0) // bt
    return first, end


def decode_blocks_run(seq_lens, *, page: int, width: int,
                      window: int | None = None) -> np.ndarray:
    """Blocks paged_flash_decode runs for each post-append length of
    `seq_lens` (host numpy; the kernel's own loop bounds)."""
    bt = page * decode_pages_per_block(page, width)
    first, end = _live_blocks(np.asarray(seq_lens, np.int64), bt, window,
                              np.maximum)
    return end - first


def _decode_lanes(d: int, page: int, n_kv: int) -> tuple[int, int]:
    """(fold, dp): how paged_flash_decode lays a page's rows across lanes.

    A DMA may slice the pool only along whole tiles (128 lanes by 8 rows),
    so each page is viewed as rows of fold * dp lanes: `fold` consecutive
    tokens of one KV head, each `dp` lanes wide.  A head dim that divides
    128 folds (fold = 128 // d: at d=64 a row holds two tokens) where the
    page then has a multiple of 8 rows; a multiple of 128 stands as it
    is; any other is zero-padded to the next multiple of 128, which costs
    a copy of the pool."""
    if d % 128 == 0:
        return 1, d
    fold = 128 // d
    if 128 % d == 0 and page % fold == 0 and n_kv * page // fold % 8 == 0:
        return fold, d
    return 1, -(-d // 128) * 128


def _paged_decode_kernel(pt_ref, sl_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                         cfg_kv, n_kv, groups, page, ppb, width, fold, dp,
                         scale, window):
    """One sequence of the paged decode grid.

    The loop runs only the blocks of `ppb` pages that hold a live token
    (_live_blocks of the prefetched seq_lens), so a page past the
    sequence's length, or wholly before its window, is neither copied nor
    computed.  Each block's pages are copied from the pool in HBM into one
    of two VMEM buffers, one copy per page for K and one for V, and the
    next block's copies start before the current block is computed.  Posit
    pages decode here, right before the dot: HBM only ever saw the narrow
    ints.

    A page is R = n_kv * rph rows of fold * dp lanes (_decode_lanes): row
    r holds tokens (r % rph) * fold + f, f < fold, of KV head r // rph.  So
    the scores of lane block f are one dot of q, placed in lane block f
    (q_ref[0, f]), with the rows of all the block's pages stacked; a score
    whose row belongs to another KV head than the query's is masked, and
    the value dot keeps lane block f of its product.
    """
    b = pl.program_id(0)
    seq_len = sl_ref[b]
    bt = ppb * page
    first, end = _live_blocks(seq_len, bt, window, jnp.maximum)
    H = n_kv * groups
    _, R, lanes = k_buf.shape[1:]
    rph = R // n_kv

    def copies(blk, slot):
        """(live, K copy, V copy) of each page of block blk into buffer
        slot; a page past the table, past seq_len or before the window is
        not live and is never copied."""
        out = []
        for i in range(ppb):
            j = blk * ppb + i
            live = (j < width) & (j * page < seq_len)
            if window is not None:
                live &= (j + 1) * page > seq_len - window
            pg = pt_ref[b, jnp.minimum(j, width - 1)]
            out.append((live,
                        pltpu.make_async_copy(k_hbm.at[pg],
                                              k_buf.at[slot, i],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[pg],
                                              v_buf.at[slot, i],
                                              sems.at[1, slot])))
        return out

    def start(blk, slot):
        for live, ck, cv in copies(blk, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(blk, slot):
        for live, ck, cv in copies(blk, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    def attended(pos):
        ok = pos < seq_len
        if window is not None:
            # local attention: the query sits at position seq_len-1 (the
            # cache is post-append), so it sees pos in (seq_len-1-window,
            # seq_len), as the blockwise decode path's `qpos - kpos <
            # window` mask does
            ok &= pos > seq_len - 1 - window
        return ok

    # block-invariant offsets: each element's position past the block's
    # first, and each score's KV head against its query's.  The block's
    # pages stack into n = ppb * R rows: row c is row c % R of page c // R
    n = ppb * R
    row = jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 0)
    kv_off = ((row // R) * page + (row % R % rph) * fold
              + jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 1) // dp)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)
    s_off = (col // R) * page + (col % R % rph) * fold
    same_head = (jax.lax.broadcasted_iota(jnp.int32, (H, n), 0) // groups
                 == col % R // rph)
    lane_fold = jax.lax.broadcasted_iota(jnp.int32, (H, lanes), 1) // dp
    qs = [q_ref[0, f].astype(jnp.float32) for f in range(fold)]

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first < end)
    def _():
        start(first, 0)

    def block(blk, carry):
        slot = (blk - first) % 2

        @pl.when(blk + 1 < end)
        def _():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        k = k_buf[slot]                               # (ppb, R, lanes)
        v = v_buf[slot]
        if cfg_kv is not None:
            k = decode_to_f32(k, cfg_kv)
            v = decode_to_f32(v, cfg_kv)
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        k, v = k.reshape(n, lanes), v.reshape(n, lanes)
        # a masked token's p is 0, but a page that was not copied, or the
        # garbage tail of a live one, may hold NaN: 0 * NaN is NaN, and a
        # folded row shares its lanes' dot with a live token
        live = attended(blk * bt + kv_off)
        k = jnp.where(live, k, 0.0)
        v = jnp.where(live, v, 0.0)

        # s[f][h, c]: query head h against stacked row c, token
        # (c % R % rph) * fold + f of KV head c % R // rph of page c // R
        s = []
        for f in range(fold):
            sf = jax.lax.dot_general(
                qs[f], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            ok = same_head & attended(blk * bt + s_off + f)
            s.append(jnp.where(ok, sf, _NEG))
        m_prev = m_ref[...][:, :1]                    # (H, 1)
        m_cur = m_prev
        for sf in s:
            m_cur = jnp.maximum(m_cur, sf.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        l_new = l_ref[...][:, :1] * alpha
        acc = acc_ref[...] * alpha
        for f, sf in enumerate(s):
            p = jnp.exp(sf - m_cur)
            l_new += p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc += jnp.where(lane_fold == f, pv, 0.0)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        return carry

    jax.lax.fori_loop(first, end, block, 0)
    l = l_ref[...][:, :1]
    o_ref[0] = acc_ref[...] / jnp.where(l == 0, 1.0, l)


def _prefill_body(sl_ref, qo_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                  cfg_kv, n_kv, groups, bq, bk,
                  nkv_blocks, scale, causal, window, softcap,
                  with_lse=False):
    """One (sequence, q-tile, kv-tile) cell of the fused prefill grid.

    Shared by the paged entry (the BlockSpec index_map resolved the KV tile
    from the scalar-prefetched page table) and the contiguous entry (the
    tile is block j of the dense cache).  Posit KV tiles decode here, in
    VMEM, right before the dot — the dense f32 view the gather_kv fallback
    materialized never exists.  GQA keeps the group dim folded into the
    query rows: q is (n_kv, groups*bq, d) so one batched dot per kv head
    feeds the MXU without repeating K/V across groups.

    with_lse: also emit the log-sum-exp rows (m + log l), the residual the
    backward kernels need to rebuild p = exp(s - lse) without re-running the
    online softmax.
    """
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref = None
        m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    # (H, bq, d) -> (n_kv, groups*bq, d): heads are (kv, group)-major, so a
    # single reshape folds the group axis into the query-row axis
    q = q_ref[0].astype(jnp.float32).reshape(n_kv, groups * bq, d)
    k = k_ref[0]
    v = v_ref[0]
    if cfg_kv is not None:
        k = decode_to_f32(k, cfg_kv)
        v = decode_to_f32(v, cfg_kv)
    else:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)

    # s[kv, g*bq + qi, p] = q . k  (batched over the kv head)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    # row r of the folded axis is query qi = r % bq of this tile
    qpos = qo_ref[b] + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, groups * bq, bk), 1) % bq
    kpos = j * bk + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, groups * bq, bk), 2)
    valid = kpos < sl_ref[b]                          # KV padding / garbage
    if causal:
        valid = valid & (qpos >= kpos)
    if window is not None:
        valid = valid & (qpos - kpos < window)
    s = jnp.where(valid, s, _NEG)

    m_prev = m_ref[...][:, :, :1]                     # (n_kv, g*bq, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
        p.sum(axis=-1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(j == nkv_blocks - 1)
    def _done():
        l = l_ref[...][:, :, :1]
        safe_l = jnp.where(l == 0, 1.0, l)
        out = acc_ref[...] / safe_l
        o_ref[0] = out.reshape(n_kv * groups, bq, d)
        if lse_ref is not None:
            # fully-masked rows (l == 0, m == -inf) get lse = 0: finite, and
            # their p = exp(_NEG - 0) underflows to exactly 0 in the backward
            m = m_ref[...][:, :, :1]
            lse = jnp.where(l == 0, 0.0, m + jnp.log(safe_l))
            lse_ref[0] = lse[..., 0].reshape(n_kv * groups, bq)


def _prefill_scratch(n_kv, groups, bq, d):
    return [
        pltpu.VMEM((n_kv, groups * bq, 128), jnp.float32),
        pltpu.VMEM((n_kv, groups * bq, 128), jnp.float32),
        pltpu.VMEM((n_kv, groups * bq, d), jnp.float32),
    ]


# batch and q-tile axes carry no state; the kv axis owns the online-softmax
# accumulators and must run in order
_PREFILL_SEMANTICS = ("parallel", "parallel", "arbitrary")

# the backward holds q, dO, K, V and the row-stat columns (lane-padded to
# 128) double-buffered, plus its f32 accumulators: ~16.3 MiB at bq=128,
# bk=256, H=15, d=64 (smollm-360m), just past Mosaic's 16 MiB default scope
_BWD_VMEM_LIMIT = 32 * 2**20


@functools.partial(
    jax.jit,
    static_argnames=("cfg_kv", "causal", "window", "softcap", "bq",
                     "interpret"),
)
def paged_flash_prefill(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, page_table: jnp.ndarray,
                        seq_lens: jnp.ndarray, q_offset: jnp.ndarray, *,
                        cfg_kv: PositConfig | None = None,
                        causal: bool = True, window: int | None = None,
                        softcap: float | None = None, bq: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused paged prefill attention (the chunked-prefill / TTFT hot path).

    q [B, H, Sq, D] x paged KV pool -> [B, H, Sq, D] f32.  The pool layout
    matches paged_flash_decode: k_pages/v_pages [num_pages, n_kv, page, D]
    (posit storage ints when cfg_kv is set), page_table [B, W] scalar-
    prefetched so the BlockSpec index map streams exactly the pages each
    sequence owns into VMEM.  seq_lens [B] is the *post-append* valid
    length (positions >= it are masked); q_offset [B] is the absolute
    position of each sequence's first query row (mid-prefill chunks:
    seq_lens - num_new).  Query rows beyond the caller's real chunk length
    compute garbage and must be ignored by the caller (the engine reads the
    last *valid* position only).  softcap/window/causal are masked
    in-kernel — the conditions that used to force the gather_kv dense
    fallback.
    """
    B, H, Sq, d = q.shape
    _, n_kv, page, _ = k_pages.shape
    _, W = page_table.shape
    groups = H // n_kv
    scale = 1.0 / (d ** 0.5)
    bq_ = min(bq, max(8, Sq))
    pq = (-Sq) % bq_
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    nq = (Sq + pq) // bq_
    grid = (B, nq, W)

    body = functools.partial(
        _prefill_body, cfg_kv=cfg_kv, n_kv=n_kv, groups=groups, bq=bq_,
        bk=page, nkv_blocks=W, scale=scale, causal=causal, window=window,
        softcap=softcap)

    def kernel(pt_ref, sl_ref, qo_ref, *rest):
        body(sl_ref, qo_ref, *rest)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, bq_, d),
                         lambda b, i, j, pt, sl, qo: (b, 0, i, 0)),
            pl.BlockSpec((1, n_kv, page, d),
                         lambda b, i, j, pt, sl, qo: (pt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, n_kv, page, d),
                         lambda b, i, j, pt, sl, qo: (pt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, bq_, d),
                               lambda b, i, j, pt, sl, qo: (b, 0, i, 0)),
        scratch_shapes=_prefill_scratch(n_kv, groups, bq_, d),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, d), jnp.float32),
        compiler_params=compiler_params(*_PREFILL_SEMANTICS),
        name="paged_flash_prefill",
        interpret=interpret,
    )(page_table, seq_lens, q_offset, q, k_pages, v_pages)
    return out[:, :, :Sq, :]


@functools.partial(
    jax.jit,
    static_argnames=("cfg_kv", "causal", "window", "softcap", "bq", "bk",
                     "return_lse", "interpret"),
)
def flash_prefill_contiguous(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             kv_len: jnp.ndarray, q_offset: jnp.ndarray, *,
                             cfg_kv: PositConfig | None = None,
                             causal: bool = True, window: int | None = None,
                             softcap: float | None = None, bq: int = 128,
                             bk: int = 256, return_lse: bool = False,
                             interpret: bool = False) -> jnp.ndarray:
    """The prefill kernel over a contiguous (dense-cache / training) KV.

    q [B, H, Sq, D] x k/v [B, n_kv, Skv, D] -> [B, H, Sq, D] f32.  Same
    kernel body as paged_flash_prefill; the KV tile index map is the block
    index instead of a page-table lookup, so the dense engine's prefill and
    the training forward stream the cache (posit ints or float) tile by
    tile without any dense f32 copy.  kv_len/q_offset [B] as in the paged
    entry (scalars must be broadcast by the caller).

    Default blocks: bq=128 query rows x bk=256 KV rows keeps the f32
    working set (decoded K+V tiles + acc + running stats) under ~2 MB for
    d=128 GQA shapes — small enough to double-buffer the posit tile
    fetches, large enough that every HBM byte feeds >= bq MXU MACs (well
    past the ~300 flops/byte ridge at posit16 width).

    return_lse: additionally return the row log-sum-exps [B, H, Sq] f32 —
    the residual the training backward saves so the dQ/dK/dV kernels can
    rebuild p = exp(s - lse) tile by tile.
    """
    B, H, Sq, d = q.shape
    _, n_kv, Skv, _ = k.shape
    groups = H // n_kv
    scale = 1.0 / (d ** 0.5)
    bq_ = min(bq, max(8, Sq))
    bk_ = min(bk, Skv)
    pq = (-Sq) % bq_
    pk = (-Skv) % bk_
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        # padded keys sit at kpos >= Skv >= kv_len and are masked in-kernel
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (Sq + pq) // bq_, (Skv + pk) // bk_
    grid = (B, nq, nk)

    body = functools.partial(
        _prefill_body, cfg_kv=cfg_kv, n_kv=n_kv, groups=groups, bq=bq_,
        bk=bk_, nkv_blocks=nk, scale=scale, causal=causal, window=window,
        softcap=softcap, with_lse=return_lse)

    o_spec = pl.BlockSpec((1, H, bq_, d), lambda b, i, j, sl, qo: (b, 0, i, 0))
    o_shape = jax.ShapeDtypeStruct((B, H, Sq + pq, d), jnp.float32)
    if return_lse:
        out_specs = [o_spec,
                     pl.BlockSpec((1, H, bq_), lambda b, i, j, sl, qo: (b, 0, i))]
        out_shape = [o_shape,
                     jax.ShapeDtypeStruct((B, H, Sq + pq), jnp.float32)]
    else:
        out_specs, out_shape = o_spec, o_shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, bq_, d),
                         lambda b, i, j, sl, qo: (b, 0, i, 0)),
            pl.BlockSpec((1, n_kv, bk_, d),
                         lambda b, i, j, sl, qo: (b, 0, j, 0)),
            pl.BlockSpec((1, n_kv, bk_, d),
                         lambda b, i, j, sl, qo: (b, 0, j, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=_prefill_scratch(n_kv, groups, bq_, d),
    )
    res = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compiler_params(*_PREFILL_SEMANTICS),
        name="flash_prefill_contiguous",
        interpret=interpret,
    )(kv_len, q_offset, q, k, v)
    if return_lse:
        out, lse = res
        return out[:, :, :Sq, :], lse[:, :, :Sq]
    return res[:, :, :Sq, :]


def _bwd_probs(q, k, lse, qo_b, sl_b, i, j, *, n_kv, groups, bq, bk, scale,
               causal, window, softcap):
    """Recompute p = exp(s - lse) for one (q-tile, kv-tile) pair with the
    forward's exact masking, plus the softcap chain factor d s_cap / d s.

    The chain factor is taken from the *unmasked* capped scores (bounded in
    [-softcap, softcap]); masked positions are killed through p alone, so no
    inf/NaN from (_NEG / softcap)**2 can leak into the products.
    """
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        s = t * softcap
        dcap = 1.0 - t * t
    else:
        dcap = None
    qpos = qo_b + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, groups * bq, bk), 1) % bq
    kpos = j * bk + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, groups * bq, bk), 2)
    valid = kpos < sl_b
    if causal:
        valid = valid & (qpos >= kpos)
    if window is not None:
        valid = valid & (qpos - kpos < window)
    p = jnp.exp(jnp.where(valid, s, _NEG) - lse)
    return p, dcap


def _prefill_bwd_dq_body(sl_ref, qo_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dq_acc, *, cfg_kv,
                         n_kv, groups, bq, bk, nkv_blocks, scale, causal,
                         window, softcap):
    """dQ tile: sweep the kv axis, accumulating ds @ K in an f32 VMEM
    scratch (the per-tile quire) and writing once at the last kv block.
    Posit KV decodes in VMEM exactly as in the forward — the backward
    never materializes an f32 cache either.
    """
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    d = q_ref.shape[-1]
    q = q_ref[0].astype(jnp.float32).reshape(n_kv, groups * bq, d)
    k = k_ref[0]
    v = v_ref[0]
    if cfg_kv is not None:
        k = decode_to_f32(k, cfg_kv)
        v = decode_to_f32(v, cfg_kv)
    else:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)

    lse = lse_ref[0].reshape(n_kv, groups * bq, 1)
    p, dcap = _bwd_probs(q, k, lse, qo_ref[b], sl_ref[b], i, j, n_kv=n_kv,
                         groups=groups, bq=bq, bk=bk, scale=scale,
                         causal=causal, window=window, softcap=softcap)
    do = do_ref[0].astype(jnp.float32).reshape(n_kv, groups * bq, d)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    delta = delta_ref[0].reshape(n_kv, groups * bq, 1)
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    # ds is d loss / d (scaled scores): one scale chains back to q
    dq_acc[...] += jax.lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale

    @pl.when(j == nkv_blocks - 1)
    def _done():
        dq_ref[0] = dq_acc[...].reshape(n_kv * groups, bq, d)


def _prefill_bwd_dkv_body(sl_ref, qo_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
                          dv_acc, *, n_kv, groups, bq, bk, nq_blocks, scale,
                          causal, window, softcap):
    """dK/dV tile: the kv tile is pinned (axis 1), the q axis sweeps (axis
    2) carrying the two f32 accumulators.  The folded (group, q-row) axis is
    the contraction, so the GQA group-sum falls out of the same reshape the
    forward uses.  Only called for float KV — posit caches carry no
    tangent.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    d = q_ref.shape[-1]
    q = q_ref[0].astype(jnp.float32).reshape(n_kv, groups * bq, d)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    lse = lse_ref[0].reshape(n_kv, groups * bq, 1)
    p, dcap = _bwd_probs(q, k, lse, qo_ref[b], sl_ref[b], i, j, n_kv=n_kv,
                         groups=groups, bq=bq, bk=bk, scale=scale,
                         causal=causal, window=window, softcap=softcap)
    do = do_ref[0].astype(jnp.float32).reshape(n_kv, groups * bq, d)
    # padded / garbage q rows contribute nothing: their do is zero-padded,
    # so p^T do and ds^T q vanish row by row
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    delta = delta_ref[0].reshape(n_kv, groups * bq, 1)
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale

    @pl.when(i == nq_blocks - 1)
    def _done():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("cfg_kv", "causal", "window", "softcap", "bq", "bk",
                     "interpret"),
)
def flash_prefill_bwd_contiguous(q: jnp.ndarray, k: jnp.ndarray,
                                 v: jnp.ndarray, o: jnp.ndarray,
                                 lse: jnp.ndarray, do: jnp.ndarray,
                                 kv_len: jnp.ndarray, q_offset: jnp.ndarray,
                                 *, cfg_kv: PositConfig | None = None,
                                 causal: bool = True,
                                 window: int | None = None,
                                 softcap: float | None = None, bq: int = 128,
                                 bk: int = 256, interpret: bool = False):
    """Backward of flash_prefill_contiguous: (dQ, dK, dV).

    Two kernels over the same tiles as the forward: dQ pins the q tile and
    sweeps kv; dK/dV pin the kv tile and sweep q.  Both rebuild the scores
    from (q, k, lse) — classic flash backward, no [Sq, Skv] matrix ever
    exists — and accumulate in per-tile f32 VMEM scratch (the PERCIVAL
    quire analogue: narrow storage, wide accumulation).  delta = rowsum
    (dO * O) is the only host-side precompute.  Posit KV (cfg_kv set)
    decodes in VMEM for dQ and returns dK = dV = None: storage ints carry
    no tangent, matching the jnp-reference oracle.
    """
    B, H, Sq, d = q.shape
    _, n_kv, Skv, _ = k.shape
    groups = H // n_kv
    scale = 1.0 / (d ** 0.5)
    bq_ = min(bq, max(8, Sq))
    bk_ = min(bk, Skv)
    pq = (-Sq) % bq_
    pk = (-Skv) % bk_

    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    lse = lse.astype(jnp.float32)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, pq), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pq)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pq)))
    # the kernels need the row stats as columns, (n_kv, groups*bq, 1): hand
    # them over as [B, H, S, 1] so the in-kernel reshape only merges leading
    # axes (Mosaic cannot relayout a lane-major (H, bq) tile into a column)
    lse, delta = lse[..., None], delta[..., None]
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (Sq + pq) // bq_, (Skv + pk) // bk_

    qd_spec = pl.BlockSpec((1, H, bq_, d), lambda b, i, j, sl, qo: (b, 0, i, 0))
    kv_spec = pl.BlockSpec((1, n_kv, bk_, d),
                           lambda b, i, j, sl, qo: (b, 0, j, 0))
    row_spec = pl.BlockSpec((1, H, bq_, 1),
                            lambda b, i, j, sl, qo: (b, 0, i, 0))

    dq = pl.pallas_call(
        functools.partial(
            _prefill_bwd_dq_body, cfg_kv=cfg_kv, n_kv=n_kv, groups=groups,
            bq=bq_, bk=bk_, nkv_blocks=nk, scale=scale, causal=causal,
            window=window, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nq, nk),
            in_specs=[qd_spec, kv_spec, kv_spec, qd_spec, row_spec, row_spec],
            out_specs=qd_spec,
            scratch_shapes=[pltpu.VMEM((n_kv, groups * bq_, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, d), jnp.float32),
        compiler_params=compiler_params(
            *_PREFILL_SEMANTICS, vmem_limit_bytes=_BWD_VMEM_LIMIT),
        name="flash_prefill_bwd_contiguous",
        interpret=interpret,
    )(kv_len, q_offset, q, k, v, do, lse, delta)[:, :, :Sq, :]

    if cfg_kv is not None:
        return dq, None, None

    # kv tile on the parallel axis 1, q sweep (with the accumulators) on
    # the trailing "arbitrary" axis
    qd_spec2 = pl.BlockSpec((1, H, bq_, d),
                            lambda b, j, i, sl, qo: (b, 0, i, 0))
    kv_spec2 = pl.BlockSpec((1, n_kv, bk_, d),
                            lambda b, j, i, sl, qo: (b, 0, j, 0))
    row_spec2 = pl.BlockSpec((1, H, bq_, 1),
                             lambda b, j, i, sl, qo: (b, 0, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _prefill_bwd_dkv_body, n_kv=n_kv, groups=groups, bq=bq_, bk=bk_,
            nq_blocks=nq, scale=scale, causal=causal, window=window,
            softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk, nq),
            in_specs=[qd_spec2, kv_spec2, kv_spec2, qd_spec2, row_spec2,
                      row_spec2],
            out_specs=[kv_spec2, kv_spec2],
            scratch_shapes=[pltpu.VMEM((n_kv, bk_, d), jnp.float32),
                            pltpu.VMEM((n_kv, bk_, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, n_kv, Skv + pk, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, n_kv, Skv + pk, d), jnp.float32)],
        compiler_params=compiler_params(
            *_PREFILL_SEMANTICS, vmem_limit_bytes=_BWD_VMEM_LIMIT),
        name="flash_prefill_bwd_contiguous",
        interpret=interpret,
    )(kv_len, q_offset, q, k, v, do, lse, delta)
    return dq, dk[:, :, :Skv, :], dv[:, :, :Skv, :]


@functools.partial(jax.jit, static_argnames=("cfg_kv", "window", "interpret"))
def paged_flash_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, page_table: jnp.ndarray,
                       seq_lens: jnp.ndarray, *,
                       cfg_kv: PositConfig | None = None,
                       window: int | None = None,
                       interpret: bool = False) -> jnp.ndarray:
    """Fused paged-gather decode attention (the continuous-batching hot path).

    q [B, H, D] x paged KV pool -> [B, H, D] f32.  k_pages/v_pages
    [num_pages, n_kv, page, D] hold posit storage ints when cfg_kv is set
    and stay in HBM; page_table [B, W] names each sequence's pages in
    position order and is scalar-prefetched with seq_lens, so the kernel
    copies exactly the pages a sequence owns — the dense `materialize_kv`
    copy never exists.  One grid step per sequence walks its live blocks of
    decode_pages_per_block(page, W) pages (~128 tokens): positions >=
    seq_lens[b] are masked, and a block with none of the sequence's
    attended positions is neither copied nor computed (decode_blocks_run
    counts them).  GQA: H = n_kv * groups, query head h reads kv head
    h // groups.  window: sliding-window (local-attention) size — the
    decode query at position seq_lens[b]-1 attends only the last `window`
    tokens, and blocks wholly before them are skipped too.
    """
    bh, H, d = q.shape
    n_pool, n_kv, page, _ = k_pages.shape
    _, W = page_table.shape
    groups = H // n_kv
    ppb = decode_pages_per_block(page, W)
    fold, dp = _decode_lanes(d, page, n_kv)
    if dp != d:
        pad = ((0, 0),) * 3 + ((0, dp - d),)
        k_pages, v_pages = jnp.pad(k_pages, pad), jnp.pad(v_pages, pad)
        q = jnp.pad(q, pad[1:])
    # the pool as rows of fold * dp lanes; q once per lane block f
    R, lanes = n_kv * page // fold, fold * dp
    k_rows = k_pages.reshape(n_pool, R, lanes)
    v_rows = v_pages.reshape(n_pool, R, lanes)
    qf = jnp.stack([jnp.pad(q, ((0, 0), (0, 0),
                                (f * dp, lanes - (f + 1) * dp)))
                    for f in range(fold)], axis=1)       # (B, fold, H, lanes)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, fold, H, lanes), lambda b, pt, sl: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, lanes), lambda b, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, R, lanes), k_pages.dtype),
            pltpu.VMEM((2, ppb, R, lanes), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, cfg_kv=cfg_kv, n_kv=n_kv,
                          groups=groups, page=page, ppb=ppb, width=W,
                          fold=fold, dp=dp, scale=1.0 / (d ** 0.5),
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, H, lanes), jnp.float32),
        compiler_params=compiler_params("parallel"),
        name="paged_flash_decode",
        interpret=interpret,
    )(page_table, seq_lens, qf, k_rows, v_rows)
    # lane block f holds the tokens of fold f: their sum is the output
    o = out[..., :d]
    for f in range(1, fold):
        o = o + out[..., f * dp:f * dp + d]
    return o


@functools.partial(
    jax.jit,
    static_argnames=("cfg_kv", "causal", "bq", "bk", "interpret"),
)
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    cfg_kv: PositConfig | None = None, causal: bool = True,
                    bq: int = 128, bk: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """q [BH, Sq, D] x k,v [BH, Skv, D] -> [BH, Sq, D].

    k/v are posit storage ints when cfg_kv is given, else float.  The causal
    mask assumes queries occupy the *last* Sq positions of the Skv context
    (prefill: Sq == Skv; decode: Sq == 1).
    """
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    bq_ = min(bq, max(8, sq))
    bk_ = min(bk, skv)
    pq = (-sq) % bq_
    pk = (-skv) % bk_
    # pad keys with zeros and mask them off via position bounds below; padded
    # queries produce garbage rows that are sliced away.
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    sqp, skvp = sq + pq, skv + pk
    grid = (bh, sqp // bq_, skvp // bk_)
    scale = 1.0 / (d ** 0.5)
    q_offset = skv - sq                       # causal alignment

    out = pl.pallas_call(
        functools.partial(_flash_kernel, cfg_kv=cfg_kv, nkv=grid[2],
                          scale=scale, causal=causal, bq=bq_, bk=bk_,
                          q_offset=q_offset, kv_len=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq_, 128), jnp.float32),
            pltpu.VMEM((bq_, 128), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        compiler_params=compiler_params(*_PREFILL_SEMANTICS),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]
