"""Serving engines: synchronized-batch (dense cache) and continuous-batching
(paged posit KV cache).

`generate` is the original synchronized engine — one batch, everyone
prefills together, everyone decodes until the longest request finishes.  It
remains the oracle the paged engine is tested against (identical batches
must produce bit-identical logits) and the baseline
benchmarks/serving_decode.py measures against.

`PagedServingEngine` is the production shape: a host-side scheduler admits
requests into sequence slots mid-flight, chunk-prefills their prompts,
decodes all active slots in one fused step over the paged pool
(serving/paged_kv.py), retires finished sequences and hands their pages to
waiting requests immediately.  On the Pallas path both step shapes are
fully fused attention: decode through paged_flash_decode, prefill chunks
(any Sq, softcap, window) through paged_flash_prefill — the gather_kv dense
materialization never runs on TPU (paged_kv.GATHER_FALLBACKS counts any
regression), so time-to-first-token streams KV at posit width end to end.  Out-of-pages triggers preemption (youngest
sequence requeued, pages freed), so the engine degrades gracefully instead
of OOMing.  Every device step runs through exactly two jitted callables
(one prefill-chunk shape, one decode shape) built once per model config and
shared across engines — zero retrace at steady state.  Two scheduling
policies keep mixed-length traffic fast: the page-table width is bucketed
to powers of two over the *participating* slots only (a short prompt's
prefill chunks never pay a 4k-token neighbor's width; bounded extra traces,
one per bucket), and admissions are batched so one prefill stall amortizes
over several waiting prompts instead of interrupting decode per freed slot.

Sampling happens on device inside the jitted step (greedy argmax or
jax.random temperature sampling): a step's device->host traffic is the
[max_seqs] int32 sampled tokens, never the [max_seqs, vocab] logits.  With
a `mesh`, the step becomes one shard_map over ("data", "model"): sequence
slots/pages data-parallel, weights Megatron tensor-parallel (see
_sharded_paged_step) — the host scheduler is a pure page/slot bookkeeper
and is identical in both modes.

Robustness contract (the chaos-hardened layer; serving/faults.py injects,
tests/test_chaos_serving.py asserts): every submitted request resolves to
exactly one structured outcome —

    completed     all requested tokens generated
    rejected      admission backpressure (bounded queue / pool capacity)
    expired       per-request deadline or step-TTL hit; partial tokens kept
    failed_nar    NaR/non-finite detected in the request's output logits
    failed_fault  its device step failed twice; the slot is quarantined

— and a drain never raises, no matter how oversubscribed the pool is or
what faults the step path throws.  NaR detection runs on device inside the
jitted step: a per-slot O(1) finiteness reduction over the last-position
logits (posit NaR decodes to NaN in the f32 logit domain, so one check
covers NaR-poisoned KV pages, activations and genuine numerical blowup)
whose [max_seqs] bool rides back with the sampled tokens — no extra host
sync on the happy path.  Outcomes and per-request partial tokens live in
`engine.outcomes`; `stats()` carries the full outcome/fault counter set.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (decode_blocks_run,
                                           decode_pages_per_block)
from repro.models.transformer import (ModelConfig, assemble_paged_caches,
                                      copy_paged_pages, extract_paged_pages,
                                      forward, init_caches, init_paged_pages,
                                      poison_paged_pages)
from repro.serving.backends import layout_for
from repro.serving.faults import InjectedFault, as_injector
from repro.serving.paged_kv import (GARBAGE_PAGE, GATHER_FALLBACKS, PagePool,
                                    PoolExhausted, reclaimable_pages)
from repro.serving.prefix_cache import RadixIndex

# python-body executions of the traced step fns — i.e. trace counts.  Tests
# assert the steady state adds zero entries here (the retrace regression).
STEP_TRACES: collections.Counter = collections.Counter()


def prefill_step(params, cfg: ModelConfig, tokens, caches):
    logits, _, caches = forward(params, cfg, tokens=tokens, caches=caches)
    return logits[:, -1], caches


def decode_step(params, cfg: ModelConfig, token, caches):
    """token [B, 1] -> (next-token logits [B, vocab], new caches)."""
    logits, _, caches = forward(params, cfg, tokens=token, caches=caches)
    return logits[:, -1], caches


def sample(logits, key, temperature: float = 0.0):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def _step_cache(maxsize: int):
    """lru_cache for the jitted step builders, keyed on the arguments *and*
    on kernels.ops.dispatch_key().  Kernel dispatch is decided at trace
    time, so a step built with the Pallas path switched off must not reuse
    a trace made with it on (or the reverse); keying here means no caller
    has to remember to pass the dispatch state."""
    def deco(build):
        cached = functools.lru_cache(maxsize=maxsize)(
            lambda dispatch, *a, **kw: build(*a, **kw))

        @functools.wraps(build)
        def get(*a, **kw):
            from repro.kernels.ops import dispatch_key
            return cached(dispatch_key(), *a, **kw)
        return get
    return deco


@_step_cache(maxsize=64)
def _dense_steps(cfg: ModelConfig):
    """Jitted prefill/decode steps, built once per model config.

    generate() used to rebuild `jax.jit(lambda ...)` wrappers per call,
    which made every call (and every distinct max_new via the fresh cache
    shape) retrace.  The lru_cache keys the jitted objects on the hashable
    ModelConfig, so steady-state serving reuses one trace per shape.

    The cache argument is donated: without it every dense step held the
    previous *and* the next KV cache live in HBM (2x the cache footprint,
    while the paged step already donated its pool); with donation XLA
    aliases the output cache onto the input buffers, asserted by
    tests/test_serving_paged.py::test_dense_steps_donate_cache_buffers."""
    def pf(p, t, c):
        STEP_TRACES[("dense_prefill", cfg.name)] += 1
        return prefill_step(p, cfg, t, c)

    def dc(p, t, c):
        STEP_TRACES[("dense_decode", cfg.name)] += 1
        return decode_step(p, cfg, t, c)

    return (jax.jit(pf, donate_argnums=(2,)),
            jax.jit(dc, donate_argnums=(2,)))


def generate(params, cfg: ModelConfig, prompts: jnp.ndarray, max_new: int,
             max_len: int | None = None, temperature: float = 0.0,
             seed: int = 0):
    """prompts [B, S] int32 -> generated [B, max_new] int32 (batched)."""
    B, S = prompts.shape
    max_len = max_len or (S + max_new)
    caches = init_caches(cfg, B, max_len, dtype=jnp.dtype(cfg.dtype))

    pf, dc = _dense_steps(cfg)

    logits, caches = pf(params, prompts, caches)
    key = jax.random.PRNGKey(seed)
    out = []
    tok = sample(logits, key, temperature)[:, None].astype(jnp.int32)
    out.append(tok)
    for i in range(max_new - 1):
        key, sub = jax.random.split(key)
        logits, caches = dc(params, tok, caches)
        tok = sample(logits, sub, temperature)[:, None].astype(jnp.int32)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


# ==========================================================================
# continuous batching over the paged pool
# ==========================================================================
def _sample_on_device(last, *, greedy: bool, temperature, seed, step_idx,
                      slot_offset, tp_axis: str | None = None,
                      vocab_sharded: bool = False):
    """Sample next tokens [B] int32 from last-position logits, inside the
    jitted step — the host never sees a [B, vocab] array (the old engine
    pulled the full logits to numpy every decode step, a blocking
    device->host sync on the hottest loop; serving.engine._sample_host
    survives only as the tests' parity oracle).

    Keyed fold_in(fold_in(PRNGKey(seed), step), global_slot): slot_offset
    is this shard's first global slot id, so the data-sharded step draws
    the same per-slot streams as the single-device one.  Vocab-sharded
    logits (TP unembed) reduce via sharded_argmax (O(B) ints cross the
    mesh) for greedy; temperature gathers the vocab shards first.
    """
    if greedy:
        if vocab_sharded:
            from repro.distributed.collectives import sharded_argmax
            return sharded_argmax(last, tp_axis)
        return jnp.argmax(last, axis=-1).astype(jnp.int32)
    if vocab_sharded:
        from repro.distributed.collectives import gather_vocab_shards
        last = gather_vocab_shards(last, tp_axis)
    B = last.shape[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step_idx)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, slot_offset + jnp.arange(B))
    logits = last / jnp.maximum(temperature, 1e-6)
    return jax.vmap(jax.random.categorical)(keys, logits).astype(jnp.int32)


def _step_body(cfg: ModelConfig, greedy: bool, p, tokens, pages, pt, sl, nn,
               temp, seed, step_idx, poison, *, slot_offset=0,
               tp_size: int = 1, vocab_sharded: bool = False, compress=None,
               with_logits: bool = False):
    """The paged serving step, shared verbatim by the single-device and the
    mesh-sharded builders (under shard_map the tensor_parallel context and
    the shard's slot_offset are the only differences — keeping one body
    means a sampling or last-position fix cannot diverge between them).

    poison [B] bool: chaos-injected NaR-poisoned activations — the flagged
    slots' last-position logits are overwritten with NaN *on device*, which
    is exactly what a NaR reaching the unembed decodes to.  Returns a third
    output, nar [B] bool: the per-slot NaR detector — one finiteness
    reduction over each slot's own logits row (posit NaR -> NaN in the f32
    logit domain, eq. (4) pattern check landed after decode), so a poisoned
    KV page, a poisoned activation or a real numerical blowup all trip it,
    and only for the slot that produced it.  The flags ride back with the
    sampled tokens; the happy path pays no extra host sync.

    with_logits: also return the [B, vocab] last-position logits (vocab-
    sharded under TP) as a fourth output — the engine's `record_logits`
    mode, which compares logits against a reference path."""
    from repro.distributed.collectives import tensor_parallel

    with tensor_parallel("model", tp_size, vocab_sharded, compress):
        caches = assemble_paged_caches(pages, pt, sl, nn)
        logits, _, new_caches = forward(p, cfg, tokens=tokens, caches=caches)
    # last *valid* position per slot (ragged prefill chunks)
    idx = jnp.clip(nn - 1, 0, tokens.shape[1] - 1)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    last = jnp.where(poison[:, None], jnp.float32(jnp.nan), last)
    nar = jnp.any(~jnp.isfinite(last), axis=-1)
    if tp_size > 1 and vocab_sharded:
        # each model member sees only its vocab shard of `last`; a NaR in
        # any shard must flag the slot on every member (O(B) ints)
        nar = jax.lax.psum(nar.astype(jnp.int32), "model") > 0
    toks = _sample_on_device(last, greedy=greedy, temperature=temp,
                             seed=seed, step_idx=step_idx,
                             slot_offset=slot_offset,
                             tp_axis="model" if tp_size > 1 else None,
                             vocab_sharded=vocab_sharded)
    # a NaR'd row samples garbage (argmax over NaNs) — the host discards
    # the token for flagged slots and fails the request instead
    if with_logits:
        return toks, nar, extract_paged_pages(new_caches), last
    return toks, nar, extract_paged_pages(new_caches)


@_step_cache(maxsize=64)
def _paged_step(cfg: ModelConfig, greedy: bool = True,
                with_logits: bool = False):
    """The fused paged serving step, jitted once per (model config, sampling
    mode) and shared by every engine instance (a per-engine jit would
    recompile identical shapes for each engine — e.g. one per benchmark
    repetition).  Returns ([max_seqs] int32 sampled tokens, [max_seqs]
    bool NaR flags, new pages) — the token ids and per-slot flags are the
    only device->host traffic a step produces, still O(max_seqs)."""
    def step(p, tokens, pages, pt, sl, nn, temp, seed, step_idx, poison):
        STEP_TRACES[("paged_step", cfg.name, tokens.shape[1],
                     pt.shape[1])] += 1
        return _step_body(cfg, greedy, p, tokens, pages, pt, sl, nn, temp,
                          seed, step_idx, poison, with_logits=with_logits)

    return jax.jit(step, donate_argnums=(2,))


@_step_cache(maxsize=16)
def _sharded_paged_step(cfg: ModelConfig, mesh, greedy: bool = True,
                        compress=None, with_logits: bool = False):
    """The mesh-sharded paged serving step: one shard_map over the
    ("data", "model") mesh, jitted once per (config, mesh, sampling mode).

    data axis:  sequence slots — tokens/page_table/seq_lens/num_new rows
        and a private page sub-pool per shard (the host scheduler allocates
        shard-locally, so table entries are local page ids everywhere).
    model axis: Megatron TP — column/row-parallel weights per
        distributed.sharding.serving_param_pspecs, kv-head-sharded pages,
        one psum per block (posit-compressed via `compress`, off by default
        to keep single-device bit-parity), vocab-parallel embed/unembed
        when the vocab divides.

    Sampling runs on device inside the shard_map (a host round-trip per
    token would serialize the mesh): the step returns only the [max_seqs]
    int32 token ids, data-sharded like the slots.  `with_logits` as for
    _step_body.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import (paged_pool_pspecs,
                                            serving_param_pspecs)

    ndata, ntp = mesh.shape["data"], mesh.shape["model"]
    vocab_sharded = ntp > 1 and cfg.vocab % ntp == 0

    def body(p, tokens, pages, pt, sl, nn, temp, seed, step_idx, poison):
        STEP_TRACES[("sharded_paged_step", cfg.name, ndata, ntp,
                     tokens.shape[1], pt.shape[1])] += 1
        return _step_body(
            cfg, greedy, p, tokens, pages, pt, sl, nn, temp, seed, step_idx,
            poison,
            slot_offset=jax.lax.axis_index("data") * tokens.shape[0],
            tp_size=ntp, vocab_sharded=vocab_sharded, compress=compress,
            with_logits=with_logits)

    def step(p, tokens, pages, pt, sl, nn, temp, seed, step_idx, poison):
        data_rows = P("data", None)
        out_specs = (P("data"), P("data"), paged_pool_pspecs(pages, mesh))
        if with_logits:
            out_specs += (P("data", "model" if vocab_sharded else None),)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(serving_param_pspecs(p, mesh), data_rows,
                      paged_pool_pspecs(pages, mesh), data_rows,
                      P("data"), P("data"), P(), P(), P(), P("data")),
            out_specs=out_specs,
            check_vma=False,
        )(p, tokens, pages, pt, sl, nn, temp, seed, step_idx, poison)

    return jax.jit(step, donate_argnums=(2,))


@functools.lru_cache(maxsize=64)
def _paged_copy(cfg: ModelConfig):
    """Jitted whole-tree page copy (the device half of copy-on-write),
    once per model config like the step fns.  Donates the pools so the
    copy aliases in place instead of doubling the pool's HBM."""
    def cp(pages, src, dst):
        return copy_paged_pages(pages, src, dst)

    return jax.jit(cp, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _sharded_paged_copy(cfg: ModelConfig, mesh):
    """shard_map page copy: src/dst are [ndata] *shard-local* page ids
    (copy-on-write never crosses sub-pools — dedup is shard-local so DP
    stays bit-parity with the single-device engine).  Shards with nothing
    to copy get (0, 0): the garbage page copied onto itself, a no-op."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import paged_pool_pspecs

    def step(pages, src, dst):
        def body(pages, src, dst):
            return copy_paged_pages(pages, src[0], dst[0])

        specs = paged_pool_pspecs(pages, mesh)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(specs, P("data"), P("data")),
                             out_specs=specs,
                             check_vma=False)(pages, src, dst)

    return jax.jit(step, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _paged_poison(cfg: ModelConfig):
    """Jitted whole-tree NaR page poison (the chaos harness's bit-flipped
    page), once per model config; donates the pools like the copy fn."""
    def po(pages, pg):
        return poison_paged_pages(pages, pg)

    return jax.jit(po, donate_argnums=(0,))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [S] int32
    max_new: int
    # tokens generated before a preemption: the resumed request re-prefills
    # prompt+prior and only owes max_new - len(prior) more tokens, but the
    # caller still receives all of them
    prior: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))
    # graceful-degradation fields: a step-based TTL and/or an absolute
    # wall-clock deadline; both survive preemption (the re-queued Request
    # keeps the original submission's clock)
    ttl_steps: int | None = None       # device steps from submission
    deadline_t: float | None = None    # absolute time.time() cutoff
    submit_step: int = 0               # engine._step_idx at submission


@dataclasses.dataclass
class RequestOutcome:
    """How one request resolved — the structured result every submission
    gets exactly one of (never an unhandled exception):

      completed     tokens == everything asked for
      rejected      backpressure: bounded queue or pool capacity; tokens
                    hold whatever was generated before the reject (empty
                    for submit-time rejections); retry_after_steps is the
                    backoff hint for queue-full rejections
      expired       deadline/TTL hit; tokens are the partial prefix
      failed_nar    NaR detected in this request's logits; tokens are the
                    clean prefix generated before the poison
      failed_fault  device step failed twice; slot quarantined
    """
    rid: int
    status: str
    tokens: np.ndarray
    detail: str = ""
    retry_after_steps: int | None = None
    step: int = 0                 # engine._step_idx at resolution
    time_s: float = 0.0           # wall clock at resolution


OUTCOMES = ("completed", "rejected", "expired", "failed_nar", "failed_fault")

# the engine's host phases (PagedServingEngine._phase): `step` is one
# scheduler iteration, the next six tile it in order, `submit` is a submit()
PHASES = ("step", "schedule", "page_in", "batch", "dispatch", "fetch",
          "commit", "submit")


@dataclasses.dataclass
class _Slot:
    req: Request
    admit_order: int
    pages: list                  # page ids owned, in position order
    prefill_pos: int = 0         # prompt tokens already written
    generated: list = dataclasses.field(default_factory=list)
    next_token: int = -1         # token to feed at the next decode step
    # prefix-cache bookkeeping: deepest radix node whose page this slot
    # holds (parent for the next registration), and the token count whose
    # pages are already registered/matched in the index
    node: object = None
    reg_pos: int = 0

    @property
    def phase(self) -> str:
        return "prefill" if self.prefill_pos < len(self.req.prompt) \
            else "decode"

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.req.max_new


class PagedServingEngine:
    """Continuous-batching serving over pluggable per-layer sequence caches.

    params/cfg as for generate().  Each layer kind maps to a
    serving/backends.py cache backend: attention layers live in the paged
    (optionally posit) KV pool; recurrent layers (rwkv6/rglru) live in a
    fixed-size posit *state pool* — one quantized state slot per sequence
    slot, O(1) in context length.  Hybrid patterns (recurrentgemma) mix
    both.  The host scheduler below is backend-agnostic: slots/admission/
    preemption are identical, paging simply no-ops for state layers (a
    state slot is owned by whichever request holds the sequence slot and is
    zeroed on first prefill chunk, so preempt/resume is resume-via-
    re-prefill with no extra bookkeeping).  The prefix cache is KV-only and
    auto-disables for patterns with recurrent layers — a state slot is not
    content-addressable by token prefix the way an immutable KV page is.
    For all-attn_local patterns (no prefix cache), fully expired
    sliding-window pages are freed eagerly after every step, so a long
    windowed decode holds O(window) pages, not O(context).

    max_seqs:     sequence slots (the fused step's batch dimension)
    page_size:    tokens per KV page
    table_width:  max pages per sequence (caps sequence length)
    num_pages:    total pool size; default fits max_seqs full-length
        sequences (+1 garbage page per data shard)
    prefill_chunk: prompt tokens written per prefill step (fixed shape)
    admit_threshold: batch admissions — hold freed slots until this many
        are free (or nothing is decoding / a prefill phase is already
        running) so one prefill stall amortizes over several prompts;
        default max_seqs // 2, 0 = admit eagerly
    prefix_cache: content-addressed prefix caching over the page pool
        (serving/prefix_cache.py), on by default.  Full pages of admitted
        prompts (and of generated continuations) register in a per-shard
        radix index keyed by a chained hash of the token chunks (keyed per
        model/KV-format/page-size); a later request's admission looks up
        its longest cached prefix, shares those pages (ref-counted) and
        starts chunked prefill at the first uncached token — warm
        time-to-first-token skips the shared prefix entirely, bit-identical
        to a cold prefill because the pages hold exactly the bits a cold
        run would recompute.  Writes into a shared page copy-on-write
        first; idle cached pages LRU-evict under pool pressure *before*
        any live sequence is preempted.  prefill_chunk is aligned down to
        a page_size multiple so the cached-page skip never splits a page.
    mesh:         a ("data", "model") jax Mesh (launch.mesh) — the fused
        step becomes one shard_map over it: sequence slots, page tables and
        a private page sub-pool per data shard; Megatron-TP weights and
        kv-head-sharded pages over the model axis (MoE blocks shard their
        *experts* over it instead — expert-parallel grouped GEMM with the
        router replicated, see models/moe.py; requires n_experts % ntp ==
        0); sampling stays on device (the step moves O(max_seqs) ints,
        never logits).  None (default): the single-device step, unchanged.
    tp_compress:  optional PositConfig — posit-compress the gather half of
        the per-block TP psums (distributed.collectives).  Profitable on
        slow inter-chip links; costs the wire quantization, so exact
        single-device parity holds only when off.
    max_waiting:  bounded admission queue (backpressure).  A submit that
        finds the queue full resolves immediately as `rejected` with a
        retry_after_steps hint instead of growing the queue without bound.
        None (default): unbounded, the pre-robustness behavior.
    default_ttl_steps / default_deadline_s: per-request defaults for
        submit()'s ttl_steps/deadline_s (None = no deadline).  An expired
        request is cancelled at the next scheduler iteration: its pages and
        state slot return to the pool and it resolves as `expired` with the
        partial tokens generated so far.
    chaos:        a serving.faults.ChaosConfig/ChaosInjector — seeded fault
        injection on the step path (simulated device failures, NaR-poisoned
        activations, bit-flipped KV pages, stragglers).  Page poison
        requires mesh=None (the injector targets shard-local page ids).
        None (default): no injection; the detection/containment paths stay
        active for real faults either way.
    record_logits: keep the logits behind every token a request is given
        (`token_logits[rid][k]`, [vocab] f32, for its k-th generated
        token) — the step then also returns its last-position logits,
        O(max_seqs * vocab) device->host per step.
        For checking logits against a reference path; off in serving.

    Observability: each step() is a profiler step span `engine.step` (its
    `kind`, prefill or decode, and `seqs` as arguments) whose child spans
    tile it in order: `engine.schedule` (retire, expire, admit, the
    page-poison hook), `engine.page_in` (allocation, preemption,
    copy-on-write and its device copies, the step's token layout),
    `engine.batch` (NaR-poison flags, the table snapshot, host->device
    transfers), `engine.dispatch` (the jitted step call), `engine.fetch`
    (the host waiting on the device for the sampled tokens) and
    `engine.commit` (seq_lens, window reclamation, emission, NaR failures,
    prefix registration); submit() is `engine.submit`.  The spans are
    always on: with no profiler attached each costs one small object.  The
    same phases keep host-time totals, stats()' `phase_ms_<name>`, and
    `step_rows` records, per step that ran the device, its kind and the
    (new tokens, tokens cached before) of every slot it computed.
    """

    def __init__(self, params, cfg: ModelConfig, *, max_seqs: int = 8,
                 page_size: int = 64, table_width: int = 16,
                 num_pages: int | None = None, prefill_chunk: int = 128,
                 temperature: float = 0.0, seed: int = 0,
                 bucket_pages: bool = True,
                 admit_threshold: int | None = None,
                 prefix_cache: bool = True,
                 mesh=None, tp_compress=None,
                 max_waiting: int | None = None,
                 default_ttl_steps: int | None = None,
                 default_deadline_s: float | None = None,
                 chaos=None, record_logits: bool = False):
        self.params, self.cfg = params, cfg
        self.max_seqs, self.page = max_seqs, page_size
        self.width = table_width
        self.layout = layout_for(cfg)
        self._needs_pages = self.layout.needs_pages
        self._recurrent = self.layout.has_state
        # chunk boundaries align to page_size multiples: warm prefill
        # resumes at a cached-page boundary, so a chunk that straddled a
        # page would re-prefill part of a cached page (or leave one
        # part-written).  Rounds down, floor one page.
        self.chunk = max(page_size, (prefill_chunk // page_size) * page_size)
        self.temperature = temperature
        self.bucket_pages = bucket_pages
        self.admit_threshold = (max_seqs // 2 if admit_threshold is None
                                else admit_threshold)
        self.mesh = mesh
        if mesh is not None:
            ndata, ntp = mesh.shape["data"], mesh.shape["model"]
            if max_seqs % ndata != 0:
                raise ValueError(f"max_seqs={max_seqs} must divide over the "
                                 f"data axis ({ndata})")
            if self._recurrent and ntp > 1:
                # sharding.py lays state pools out head-sharded on the
                # model axis, but the serving step's TP contexts only wrap
                # the attention/MLP projections — recurrent serving shards
                # data-parallel only (strategy_for makes the same call for
                # training).  Reject rather than silently mis-shard.
                raise ValueError(
                    "recurrent/hybrid patterns serve data-parallel only; "
                    f"use a mesh with model axis 1 (got {ntp})")
            dims = [(cfg.n_heads, "n_heads"), (cfg.n_kv, "n_kv")]
            if cfg.moe is None:
                dims.append((cfg.d_ff, "d_ff"))
            else:
                # MoE blocks shard the *expert* dim over the model axis
                # (expert-parallel grouped GEMM, one psum per block); each
                # expert's d_ff stays whole on its shard
                dims.append((cfg.moe.n_experts, "moe.n_experts"))
            for dim, nm in dims:
                if dim % ntp != 0:
                    raise ValueError(f"cfg.{nm}={dim} must divide the model "
                                     f"axis ({ntp}) for TP serving")
            self.n_shards = ndata
        else:
            self.n_shards = 1
        self.slots_per_shard = max_seqs // self.n_shards
        if num_pages is None:
            if self._needs_pages:
                num_pages = self.n_shards * (self.slots_per_shard
                                             * table_width + 1)
            else:
                # pure-recurrent: no KV layer reads the pool; keep the
                # garbage page plus one allocatable page per shard so the
                # page bookkeeping stays well-formed at negligible cost
                num_pages = 2 * self.n_shards
        if num_pages % self.n_shards != 0:
            raise ValueError(f"num_pages={num_pages} must divide over the "
                             f"data axis ({self.n_shards})")
        self.num_pages = num_pages
        self.pages_per_shard = num_pages // self.n_shards
        self.pages = init_paged_pages(cfg, num_pages, page_size,
                                      dtype=jnp.dtype(cfg.dtype),
                                      max_seqs=max_seqs)
        if mesh is not None:
            from repro.distributed.sharding import (paged_pool_pspecs,
                                                    serving_param_pspecs,
                                                    to_shardings)
            self.pages = jax.device_put(
                self.pages,
                to_shardings(paged_pool_pspecs(self.pages, mesh), mesh))
            # place the weights per the TP specs once, up front: params
            # committed to one device would otherwise be resharded onto the
            # mesh by GSPMD at *every* step call — O(param bytes) per decode
            # step on the loop this engine keeps at O(max_seqs) ints
            self.params = jax.device_put(
                self.params,
                to_shardings(serving_param_pspecs(self.params, mesh), mesh))
        # host scheduler state; local page 0 of every shard is its reserved
        # garbage page, and the table holds *shard-local* page ids (the
        # device step only ever sees its own sub-pool)
        self._pools = [PagePool(self.pages_per_shard)
                       for _ in range(self.n_shards)]
        # one radix index per data shard: page ids are shard-local and
        # pages cannot migrate between sub-pools, so dedup staying
        # shard-local is what keeps DP bit-parity with one device
        self._prefix = None
        # page copy fn: COW for the prefix cache, and NaR-page scrubbing
        # when a failed request's pages return to the pool
        self._copy_fn = None
        if self._needs_pages:
            self._copy_fn = (_paged_copy(cfg) if mesh is None
                             else _sharded_paged_copy(cfg, mesh))
        if prefix_cache and not self.layout.supports_prefix_cache:
            # state slots are mutable accumulators, not content-addressed
            # immutable pages — prefix caching cleanly no-ops for any
            # pattern with recurrent layers
            prefix_cache = False
        if prefix_cache:
            key = (f"{cfg.name}|kv={cfg.policy.kv_cache}|page={page_size}"
                   f"|n_kv={cfg.n_kv}|hd={cfg.hd}")
            self._prefix = [RadixIndex(key, page_size)
                            for _ in range(self.n_shards)]
        self.table = np.zeros((max_seqs, table_width), np.int32)
        self.seq_lens = np.zeros((max_seqs,), np.int32)
        self.slots: list[_Slot | None] = [None] * max_seqs
        self.waiting: deque[Request] = deque()
        self._admitted = 0
        self._next_rid = 0
        self._rng = np.random.default_rng(seed)
        self._seed = int(seed) % (2 ** 31 - 1)
        self._step_idx = 0
        self.finished: dict[int, np.ndarray] = {}
        self.outcomes: dict[int, RequestOutcome] = {}
        self.record_logits = record_logits
        self.token_logits: dict[int, list[np.ndarray]] = {}
        self.max_waiting = max_waiting
        self.default_ttl_steps = default_ttl_steps
        self.default_deadline_s = default_deadline_s
        self._quarantined: set[int] = set()
        self._chaos = as_injector(chaos)
        self._poison_fn = None
        if self._chaos is not None and self._chaos.cfg.p_page_poison > 0:
            if mesh is not None:
                raise ValueError("page-poison injection targets shard-local "
                                 "page ids; run chaos page poison with "
                                 "mesh=None")
            if self._needs_pages:
                self._poison_fn = _paged_poison(cfg)
        self.counters = collections.Counter()
        self._gather_base = self._moe_base = self._rec_base = 0
        # eager sliding-window page reclamation: sound only when *every*
        # attention layer is windowed (a full-attn layer still reads old
        # pages) and the prefix cache is off (a cached page must stay
        # resident for future prefix hits, not be recycled)
        attn_kinds = [k for k in cfg.block_pattern
                      if k in ("attn", "attn_local")]
        self._reclaim_window = (
            cfg.window
            if (attn_kinds and all(k == "attn_local" for k in attn_kinds)
                and cfg.window and self._prefix is None)
            else None)
        # attention layers per window that decode through paged_flash_decode
        # (stats()' decode_kv_blocks_*); none where decode takes the jnp path
        from repro.kernels import ops as kops
        self._decode_windows = collections.Counter(
            cfg.window if k == "attn_local" else None
            for k in (cfg.block_pattern[i % len(cfg.block_pattern)]
                      for i in range(cfg.n_layers))
            if k in ("attn", "attn_local")
        ) if kops.use_pallas() and not kops.force_reference() else {}
        self._step_width = table_width
        self.reset_stats()

        greedy = temperature <= 0.0
        if mesh is None:
            self._step_fn = _paged_step(cfg, greedy, record_logits)
        else:
            self._step_fn = _sharded_paged_step(cfg, mesh, greedy,
                                                tp_compress, record_logits)

    # ---- host-side paging ------------------------------------------------
    def _shard(self, i: int) -> int:
        """Data shard owning sequence slot i (0 when unsharded)."""
        return i // self.slots_per_shard

    @property
    def free_pages(self) -> list[int]:
        """All free (shard-local) page ids, across shards.  Idle *cached*
        prefix pages are not free — they are resident until evicted (see
        cached_pages)."""
        return [p for pool in self._pools for p in pool.free_list]

    @property
    def cached_pages(self) -> int:
        """Pages pinned by the prefix index across shards (some may also
        be live-referenced by sequences)."""
        return sum(pool.n_cached for pool in self._pools)

    def _evict_one(self, shard: int) -> bool:
        """LRU-evict one idle cached prefix page from `shard`'s index back
        to the free stack.  Runs *before* preemption ever does: a cached
        page nobody references must die before live work is rolled back."""
        if self._prefix is None:
            return False
        pool = self._pools[shard]
        pg = self._prefix[shard].evict_lru(pool.is_idle)
        if pg is None:
            return False
        pool.uncache(pg)
        self.counters["evicted_pages"] += 1
        return True

    def _alloc_page(self, i: int) -> int:
        """One fresh page for slot i's shard: the free stack, else LRU
        eviction of idle cached prefix pages, else preemption of a live
        sequence (strictly in that order).  Raises PoolExhausted when all
        three run dry — slot i alone exceeds its shard's pool — which the
        scheduler converts into a structured `rejected` outcome for slot
        i's request (never an unhandled exception out of a drain)."""
        pool = self._pools[self._shard(i)]
        while True:
            pg = pool.try_alloc()
            if pg is not None:
                return pg
            if self._evict_one(self._shard(i)):
                continue
            if not self._preempt(exclude=i):
                raise PoolExhausted(
                    "KV pool exhausted and nothing left to evict or "
                    "preempt; grow num_pages or lower max_seqs")

    def _ensure_pages(self, i: int, upto: int):
        """Slot i needs capacity for `upto` tokens; allocate from its
        shard's sub-pool (evicting idle cached pages, then preempting
        within the shard, if it runs dry)."""
        slot = self.slots[i]
        if not self._needs_pages:
            return                   # state-pool-only layout: no KV pages
        need = -(-upto // self.page)
        if need > self.width:
            raise ValueError(f"request {slot.req.rid}: {upto} tokens exceed "
                             f"table_width*page_size = {self.width * self.page}")
        while len(slot.pages) < need:
            pg = self._alloc_page(i)
            self.table[i, len(slot.pages)] = pg
            slot.pages.append(pg)

    def _free_slot(self, i: int):
        slot = self.slots[i]
        pool = self._pools[self._shard(i)]
        for pg in slot.pages:
            if pg:                   # 0 = reclaimed-window placeholder
                pool.decref(pg)      # cached prefix pages stay resident
        self.table[i, :] = 0
        self.seq_lens[i] = 0
        self.slots[i] = None

    def _maybe_cow(self, i: int):
        """Copy-on-write: the next step writes slot i's KV starting at
        seq_lens[i]; when that lands *mid-page* in a page the prefix index
        or another sequence shares, copy the page device-side and point
        slot i's table entry at the private copy first.  (Writes starting
        at a page boundary always land in a freshly allocated page, so
        only the first page of the write range can ever be shared.)"""
        slot = self.slots[i]
        if self._prefix is None or slot is None:
            return
        p0 = int(self.seq_lens[i])
        j = p0 // self.page
        if p0 % self.page == 0 or j >= len(slot.pages):
            return
        pg = slot.pages[j]
        pool = self._pools[self._shard(i)]
        if pool.ref_count(pg) <= 1 and not pool.is_cached(pg):
            return                   # private page: write in place
        new = self._alloc_page(i)
        self._device_copy(self._shard(i), pg, new)
        pool.decref(pg)
        slot.pages[j] = new
        self.table[i, j] = new
        self.counters["cow_copies"] += 1

    def _device_copy(self, shard: int, src: int, dst: int):
        """Device page copy (bit-exact for posit pages: raw bits move)."""
        if self.mesh is None:
            self.pages = self._copy_fn(self.pages, jnp.int32(src),
                                       jnp.int32(dst))
        else:
            s = np.zeros((self.n_shards,), np.int32)
            d = np.zeros((self.n_shards,), np.int32)
            s[shard], d[shard] = src, dst      # others: garbage no-op copy
            self.pages = self._copy_fn(self.pages, jnp.asarray(s),
                                       jnp.asarray(d))

    def _attach_prefix(self, i: int):
        """Longest-cached-prefix attach at admission: share the matched
        pages (ref-counted) and start chunked prefill at the first
        uncached token.  At least one prompt token is always re-fed so the
        step produces first-token logits — a fully cached page-aligned
        prompt keeps all its pages and re-feeds only the final token
        (whose mid-page write then triggers copy-on-write)."""
        slot = self.slots[i]
        if self._prefix is None:
            return
        shard = self._shard(i)
        idx, pool = self._prefix[shard], self._pools[shard]
        pages, node = idx.lookup(slot.req.prompt, self._step_idx)
        L = len(slot.req.prompt)
        cached = min(len(pages) * self.page, L - 1)
        if not pages or cached <= 0:
            self.counters["prefix_misses"] += 1
            return
        for j, pg in enumerate(pages):
            pool.incref(pg)
            self.table[i, j] = pg
        slot.pages = list(pages)
        slot.node = node
        slot.reg_pos = len(pages) * self.page
        slot.prefill_pos = cached
        self.seq_lens[i] = cached
        self.counters["prefix_hits"] += 1
        self.counters["prefix_hit_tokens"] += cached

    def _register(self, i: int):
        """Register slot i's newly filled pages in its shard's radix index
        (each page's content address covers the whole token prefix it
        completes).  An identical page already cached gets *adopted*: the
        slot's table entry swaps to the existing page and its own copy
        frees — safe because both hold bit-identical KV."""
        slot = self.slots[i]
        if self._prefix is None or slot is None:
            return
        written = int(self.seq_lens[i])
        if slot.reg_pos + self.page > written:
            return
        shard = self._shard(i)
        idx, pool = self._prefix[shard], self._pools[shard]
        if slot.node is None:
            slot.node = idx.root
        stream = np.concatenate([slot.req.prompt,
                                 np.asarray(slot.generated, np.int32)])
        while slot.reg_pos + self.page <= written:
            j = slot.reg_pos // self.page
            chunk = stream[slot.reg_pos:slot.reg_pos + self.page]
            node, existing = idx.insert(slot.node, chunk, slot.pages[j],
                                        self._step_idx)
            if existing is not None and existing != slot.pages[j]:
                pool.incref(existing)
                pool.decref(slot.pages[j])     # private copy -> freed
                slot.pages[j] = existing
                self.table[i, j] = existing
                self.counters["deduped_pages"] += 1
            elif existing is None:
                pool.cache(slot.pages[j])
            slot.node = node
            slot.reg_pos += self.page

    def _preempt(self, exclude: int) -> bool:
        """Evict the youngest other sequence *in the same shard* (pages
        cannot migrate between sub-pools): free its pages and requeue it
        (prompt + generated so far) at the front of the wait queue."""
        shard = self._shard(exclude)
        victims = [(s.admit_order, i) for i, s in enumerate(self.slots)
                   if s is not None and i != exclude
                   and self._shard(i) == shard]
        if not victims:
            return False
        _, i = max(victims)
        slot = self.slots[i]
        req = slot.req
        # restart from the full prompt + whatever was already generated
        gen = np.asarray(slot.generated, np.int32)
        new_prompt = np.concatenate([req.prompt, gen])
        remaining = req.max_new - len(slot.generated)
        self.waiting.appendleft(Request(req.rid, new_prompt, remaining,
                                        prior=np.concatenate([req.prior,
                                                              gen]),
                                        ttl_steps=req.ttl_steps,
                                        deadline_t=req.deadline_t,
                                        submit_step=req.submit_step))
        self._free_slot(i)
        self.counters["preempted"] += 1
        return True

    # ---- structured outcomes / graceful degradation ----------------------
    def _resolve(self, req: Request, status: str, detail: str = "",
                 retry_after: int | None = None, generated=None):
        """Record request `req`'s terminal outcome (exactly one per rid).
        `generated` is the token list/array produced since the last
        (re-)admission; the caller's view is always prior + generated."""
        gen = np.asarray([] if generated is None else generated, np.int32)
        toks = np.concatenate([req.prior, gen]) if len(req.prior) else gen
        self.outcomes[req.rid] = RequestOutcome(
            rid=req.rid, status=status, tokens=toks, detail=detail,
            retry_after_steps=retry_after, step=self._step_idx,
            time_s=time.time())
        self.counters[status] += 1
        if status == "completed":
            self.finished[req.rid] = toks

    def _fail_slot(self, i: int, status: str, detail: str):
        """Resolve slot i's request as `status` (partial tokens kept) and
        hand every resource it held back to the pool.  NaR-failed slots
        scrub their private pages first — see _scrub_slot_pages."""
        slot = self.slots[i]
        if status == "failed_nar":
            self._scrub_slot_pages(i)
        self._resolve(slot.req, status, detail=detail,
                      generated=slot.generated)
        self._free_slot(i)

    def _scrub_slot_pages(self, i: int):
        """Overwrite a NaR'd sequence's *private* pages with the garbage
        page's (finite) bits before they return to the free list.

        Recycled pages are never *read as valid* — the attention masks
        exclude their positions — but masked positions still multiply into
        the value aggregation as exp(-inf) = 0 times v, and 0 * NaN is
        NaN: finite stale garbage in a recycled page is harmless, NaR/NaN
        bits would poison the page's next owner.  Shared/cached pages were
        written by healthy requests (a failed slot never registers pages,
        and mid-page writes COW first), so private pages are exactly the
        set the NaR'd request may have contaminated."""
        if not self._needs_pages or self._copy_fn is None:
            return
        shard = self._shard(i)
        pool = self._pools[shard]
        for pg in self.slots[i].pages:
            if pg and pool.ref_count(pg) == 1 and not pool.is_cached(pg):
                self._device_copy(shard, GARBAGE_PAGE, pg)
                self.counters["scrubbed_pages"] += 1

    def _quarantine(self, participants):
        """A step failed twice: fail its surviving participants loudly and
        quarantine their slots (a quarantined slot is never re-admitted —
        the model of a sick device lane).  The engine keeps serving on the
        remaining slots; with none left, waiting requests reject at
        admission instead of hanging."""
        for i in list(participants):
            if self.slots[i] is None:
                continue
            self._fail_slot(i, "failed_fault",
                            "device step failed twice; slot quarantined")
            self._quarantined.add(i)
            self.counters["slots_quarantined"] += 1

    def _expired(self, req: Request, now: float) -> bool:
        if (req.ttl_steps is not None
                and self._step_idx - req.submit_step >= req.ttl_steps):
            return True
        return req.deadline_t is not None and now >= req.deadline_t

    def _expire_deadlines(self):
        """Cancel active and waiting requests whose TTL/deadline passed:
        pages and state slots return to the pool immediately, the request
        resolves as `expired` with its partial tokens."""
        now = time.time()
        for i, slot in enumerate(self.slots):
            if slot is not None and self._expired(slot.req, now):
                self._fail_slot(i, "expired", "deadline/TTL exceeded")
        kept = deque()
        for req in self.waiting:
            if self._expired(req, now):
                self._resolve(req, "expired",
                              "deadline/TTL exceeded while queued")
            else:
                kept.append(req)
        self.waiting = kept

    def _maybe_poison_page(self):
        """Chaos page-poison injection: flip one live page to NaR before
        the step.  The victim is the lowest active slot's first fully
        written, *unshared and uncached* page (containment must hold: a
        shared page would legitimately fail every reader); no candidate —
        no injection."""
        if self._chaos is None or self._poison_fn is None:
            return
        victim = None
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            pool = self._pools[self._shard(i)]
            full = int(self.seq_lens[i]) // self.page
            for j in range(min(full, len(slot.pages))):
                pg = slot.pages[j]
                if pg and pool.ref_count(pg) == 1 and not pool.is_cached(pg):
                    victim = pg
                    break
            if victim is not None:
                break
        # candidate first, injector second: a step with nothing safely
        # poisonable must not consume the injection budget
        if victim is None or not self._chaos.page_poison(self._step_idx):
            return
        self.pages = self._poison_fn(self.pages, jnp.int32(victim))
        self.counters["injected_page_poisons"] += 1

    def _retry_after_hint(self) -> int:
        """Backoff hint for queue-full rejections: device steps until the
        fastest active request can retire its slot (>= 1)."""
        remaining = [s.req.max_new - len(s.generated)
                     for s in self.slots if s is not None]
        return max(1, min(remaining, default=1))

    def _admit(self):
        if not self.waiting:
            return
        # admission batching: a mid-flight admission stalls every decoding
        # slot for the new prompt's chunk steps, so hold freed slots until
        # several can prefill together.  Admit immediately when a prefill
        # phase is already running (joining it is ~free), when nothing is
        # decoding (nothing to stall), or when enough slots accumulated.
        phases = [s.phase for s in self.slots if s is not None]
        n_free = self.max_seqs - len(phases) - len(self._quarantined)
        if ("decode" in phases and "prefill" not in phases
                and n_free < max(1, self.admit_threshold)):
            return
        while self.waiting:
            req = self.waiting[0]
            # pick the free slot whose shard caches the longest prefix of
            # this prompt (ties -> lowest slot, the pre-prefix-cache
            # behavior); a slot only qualifies when the pages the prompt
            # still needs fit its shard's free + evictable headroom
            best = None
            for i in range(self.max_seqs):
                if self.slots[i] is not None or i in self._quarantined:
                    continue
                pool = self._pools[self._shard(i)]
                hit = (self._prefix[self._shard(i)].probe(req.prompt)
                       if self._prefix is not None else 0)
                n_match = hit // self.page
                need = -(-(len(req.prompt) + 1) // self.page) - n_match
                avail = pool.n_free + max(0, pool.n_evictable - n_match)
                if self._needs_pages and need > avail:
                    continue
                cached = min(hit, len(req.prompt) - 1)
                if best is None or (cached, -i) > best[0]:
                    best = ((cached, -i), i)
            if best is None:
                if self.active == 0:
                    # nothing running and still no slot fits: this request
                    # can never be placed (pool too small for it alone, or
                    # every slot quarantined).  Structured rejection, not a
                    # crash — the drain keeps going.
                    self.waiting.popleft()
                    self._resolve(
                        req, "rejected",
                        detail=f"does not fit the idle pool "
                               f"({len(self.free_pages)} free pages across "
                               f"{self.n_shards} shard(s), "
                               f"{len(self._quarantined)} slot(s) "
                               f"quarantined)")
                    continue
                return
            i = best[1]
            self.waiting.popleft()
            self.slots[i] = _Slot(req=req, admit_order=self._admitted,
                                  pages=[])
            self._admitted += 1
            self.counters["admitted"] += 1
            if self._recurrent:
                # the sequence slot *is* the state-pool slot; its state
                # leaves are zeroed device-side on the first prefill chunk
                # (seq_lens == 0 -> backends.zero_fresh)
                self.counters["state_slot_allocs"] += 1
            self._attach_prefix(i)

    # ---- public API ------------------------------------------------------
    def submit(self, prompt, max_new: int, rid: int | None = None, *,
               ttl_steps: int | None = None,
               deadline_s: float | None = None) -> int:
        """Queue a request.  Malformed input (empty prompt, max_new < 1,
        rid collision) still raises ValueError — those are caller bugs.
        Load conditions never raise: a full wait queue or an over-capacity
        request resolves to a structured `rejected` outcome instead.

        `ttl_steps` / `deadline_s` bound the request's lifetime (device
        steps from now / wall-clock seconds from now); either hitting its
        limit cancels the request (`expired`), returning its pages and
        state slots to the pool.  Defaults come from the engine ctor."""
        with self._phase("submit"):
            return self._submit(prompt, max_new, rid, ttl_steps,
                                deadline_s)

    def _submit(self, prompt, max_new: int, rid: int | None,
                ttl_steps: int | None, deadline_s: float | None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            # an empty prompt would enter decode with the -1 sentinel as a
            # real token (wrapping to the last vocab row); reject instead
            raise ValueError("prompt must contain at least one token")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if rid is None:
            rid = self._next_rid
        elif (rid in self.finished or rid in self.outcomes
              or any(r.rid == rid for r in self.waiting)
              or any(s is not None and s.req.rid == rid
                     for s in self.slots)):
            # a colliding rid would silently overwrite the other request's
            # results in `finished`
            raise ValueError(f"request id {rid} is already in use")
        self._next_rid = max(self._next_rid, rid + 1)
        self.counters["submitted"] += 1
        if ttl_steps is None:
            ttl_steps = self.default_ttl_steps
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = Request(rid, prompt, max_new, ttl_steps=ttl_steps,
                      deadline_t=(None if deadline_s is None
                                  else time.time() + deadline_s),
                      submit_step=self._step_idx)
        if self._needs_pages and len(prompt) + max_new > self.width * self.page:
            # page-table capacity only binds layouts with KV layers; pure
            # state-pool sequences are O(1) in length.  No amount of
            # waiting makes this fit -> immediate structured rejection.
            self._resolve(req, "rejected",
                          detail=f"prompt+max_new = {len(prompt) + max_new} "
                                 f"exceeds per-sequence capacity "
                                 f"{self.width * self.page}")
            return rid
        if (self.max_waiting is not None
                and len(self.waiting) >= self.max_waiting):
            # bounded admission queue: shed load *now* with a backoff hint
            # instead of growing the queue without bound
            self._resolve(req, "rejected",
                          detail=f"wait queue full "
                                 f"({len(self.waiting)} waiting)",
                          retry_after=self._retry_after_hint())
            return rid
        if self._prefix is not None:
            # submit-time longest-cached-prefix probe (read-only: the
            # authoritative, LRU-touching lookup happens at admission,
            # when the slot — hence the shard — is known)
            self.counters["prefix_probe_tokens"] += max(
                idx.probe(prompt) for idx in self._prefix)
        self.waiting.append(req)
        return rid

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    # ---- observability ---------------------------------------------------
    def stats(self) -> dict:
        """Scheduler + prefix-cache counters and the phase clocks (the
        serving bench prints this).  Fallback counters are process-global;
        they are reported as deltas since engine construction or the last
        reset_stats()."""
        from repro.kernels.ops import RECURRENT_FALLBACKS
        from repro.models.moe import DENSE_MOE_FALLBACKS
        d = {k: 0 for k in ("admitted", "preempted",
                            "prefill_steps", "decode_steps",
                            "prefix_hits", "prefix_misses",
                            "prefix_hit_tokens", "prefix_probe_tokens",
                            "evicted_pages", "cow_copies",
                            "deduped_pages", "state_slot_allocs",
                            "expired_page_frees",
                            # robustness: outcome taxonomy (sums to
                            # `submitted`) + fault/degradation telemetry
                            "submitted", *OUTCOMES,
                            "step_retries", "slots_quarantined",
                            "scrubbed_pages",
                            "straggler_steps", "injected_step_faults",
                            "injected_nar_poisons",
                            "injected_page_poisons",
                            "decode_kv_blocks_run",
                            "decode_kv_blocks_grid")}
        d.update(self.counters)
        d["gather_fallbacks"] = (sum(GATHER_FALLBACKS.values())
                                 - self._gather_base)
        d["dense_moe_fallbacks"] = (sum(DENSE_MOE_FALLBACKS.values())
                                    - self._moe_base)
        d["recurrent_fallbacks"] = (sum(RECURRENT_FALLBACKS.values())
                                    - self._rec_base)
        d["free_pages"] = sum(p.n_free for p in self._pools)
        d["cached_pages"] = self.cached_pages
        if self._step_lat_s:
            lat = np.percentile(np.asarray(self._step_lat_s), [50, 99])
            d["step_latency_p50_ms"] = float(lat[0]) * 1e3
            d["step_latency_p99_ms"] = float(lat[1]) * 1e3
        else:
            d["step_latency_p50_ms"] = d["step_latency_p99_ms"] = 0.0
        for name in PHASES:
            d[f"phase_ms_{name}"] = self._phase_s[name] * 1e3
        return d

    def reset_stats(self):
        """Zero the counters and re-baseline the global fallback counters
        (the tests' reset hook; several drains can share one engine)."""
        from repro.kernels.ops import RECURRENT_FALLBACKS
        from repro.models.moe import DENSE_MOE_FALLBACKS
        self.counters.clear()
        # per step that ran the device: its engine.step phase reading, and
        # (kind, ((new tokens, tokens cached before) per slot computed))
        self._step_lat_s: collections.deque = collections.deque(maxlen=4096)
        self.step_rows: collections.deque = collections.deque(maxlen=4096)
        self._phase_s = dict.fromkeys(PHASES, 0.0)
        self._gather_base = sum(GATHER_FALLBACKS.values())
        self._moe_base = sum(DENSE_MOE_FALLBACKS.values())
        self._rec_base = sum(RECURRENT_FALLBACKS.values())

    @contextlib.contextmanager
    def _phase(self, name: str, span=None):
        """Run the block inside the profiler span `engine.<name>` (or
        `span`, given) and add its host time to phase clock `name`
        (stats()' `phase_ms_<name>`); yields the span."""
        if span is None:
            span = jax.profiler.TraceAnnotation(f"engine.{name}")
        t0 = time.perf_counter()
        try:
            with span:
                yield span
        finally:
            self._phase_s[name] += time.perf_counter() - t0

    def _sample_host(self, logits_row: np.ndarray) -> int:
        """Host-side sampling oracle.  The engine samples on device inside
        the jitted step (_sample_on_device) — this survives only so tests
        can check greedy parity against independently computed logits."""
        if self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / self.temperature
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _table_view(self, participants):
        """Power-of-two bucketed page-table slice sized to the sequences
        that actually compute this step (each bucket compiles once).

        Prefill steps pass only the prefilling slots: a short prompt then
        pays its own width even while a 4k-token sequence sits in a decode
        slot (that slot's num_new is 0 — its outputs are ignored and its
        writes dropped, so truncating its pages out of the view is safe)."""
        if not self.bucket_pages:
            return self.table
        used = max([len(self.slots[i].pages) for i in participants
                    if self.slots[i] is not None], default=1)
        w = 1
        while w < max(used, 1):
            w *= 2
        w = min(max(w, 1), self.width)
        return self.table[:, :w]

    def _run_step(self, tokens: np.ndarray, num_new: np.ndarray,
                  participants):
        """Run the fused step, in the batch, dispatch and fetch phases;
        returns (tokens, nar) — the sampled token and the on-device
        NaR-detector flag per slot ([max_seqs] int32 / bool, fetched in one
        transfer, so the happy path costs no extra host sync; under
        record_logits the step's last-position logits land in
        self._last_logits).  A step that fails (InjectedFault before the
        device call) is retried once against unchanged state; a repeat
        failure quarantines the participants and returns (None, None)."""
        with self._phase("batch"):
            poisoned: list[int] = []
            if self._chaos is not None:
                poisoned = self._chaos.poison_slots(self._step_idx,
                                                    participants)
            poison = np.zeros((self.max_seqs,), bool)
            poison[poisoned] = True
            # snapshot the host tables, which the host edits in place: on
            # the CPU backend jnp.asarray may alias a numpy buffer
            tok_in = jnp.asarray(tokens)
            table = np.array(self._table_view(participants))
            self._step_width = table.shape[1]
            inputs = (jnp.asarray(table),
                      jnp.asarray(self.seq_lens.copy()), jnp.asarray(num_new),
                      jnp.float32(self.temperature), jnp.int32(self._seed),
                      jnp.int32(self._step_idx), jnp.asarray(poison))
        with self._phase("dispatch"):
            for attempt in (0, 1):
                try:
                    if self._chaos is not None:
                        nap = self._chaos.straggle(self._step_idx, attempt)
                        if nap > 0.0:
                            self.counters["straggler_steps"] += 1
                            time.sleep(nap)
                        if self._chaos.step_fault(self._step_idx, attempt):
                            self.counters["injected_step_faults"] += 1
                            raise InjectedFault(
                                f"injected device failure at step "
                                f"{self._step_idx} attempt {attempt}")
                    toks, bad, self.pages, *last = self._step_fn(
                        self.params, tok_in, self.pages, *inputs)
                    break
                except InjectedFault:
                    if attempt == 0:
                        self.counters["step_retries"] += 1
                        continue
                    self._quarantine(participants)
                    return None, None
            self.counters["injected_nar_poisons"] += len(poisoned)
            self._step_idx += 1
            del tok_in, inputs       # their release is this phase's work
        with self._phase("fetch"):
            toks, bad, last = jax.device_get((toks, bad, last))
        if last:
            self._last_logits = np.asarray(last[0])
        return np.asarray(toks), np.asarray(bad)

    def _count_decode_blocks(self):
        """stats()' decode_kv_blocks_run: the blocks of pages that this
        decode step's paged_flash_decode ran, over every attention layer
        (seq_lens is post-append here, as the kernel saw it); and
        decode_kv_blocks_grid: the blocks that a static grid of such
        blocks over every slot's whole table view would run.  On a mesh
        each device of the model axis runs these blocks for its KV
        heads."""
        w = self._step_width
        blocks = -(-w // decode_pages_per_block(self.page, w))
        for window, layers in self._decode_windows.items():
            run = decode_blocks_run(self.seq_lens, page=self.page, width=w,
                                    window=window)
            self.counters["decode_kv_blocks_run"] += layers * int(run.sum())
            self.counters["decode_kv_blocks_grid"] += (layers * blocks
                                                       * self.max_seqs)

    def _keep_logits(self, i: int):
        """record_logits: keep slot i's last-step logits, which produced the
        token it is about to be given."""
        if self.record_logits:
            self.token_logits.setdefault(self.slots[i].req.rid, []).append(
                self._last_logits[i])

    def _reclaim_expired(self):
        """Free KV pages every token of which has slid out of the attention
        window (all-attn_local patterns, prefix cache off — see __init__).
        Freed table entries point at the garbage page; the window mask
        already excludes those positions on every attention path (Pallas
        decode/prefill kernels and the jnp fallback), so recycled pages can
        hold another sequence's KV without being read.  slot.pages keeps a
        0 placeholder so later positions stay index-aligned."""
        if self._reclaim_window is None:
            return
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            n = reclaimable_pages(int(self.seq_lens[i]),
                                  self._reclaim_window, self.page)
            pool = self._pools[self._shard(i)]
            for j in range(min(n, len(slot.pages))):
                pg = slot.pages[j]
                if pg:
                    pool.decref(pg)
                    slot.pages[j] = 0
                    self.table[i, j] = 0
                    self.counters["expired_page_frees"] += 1

    def _page_in(self, i: int) -> bool:
        """Allocate slot i's pages for its next write and run COW; a dry
        pool (slot i alone exceeds its shard) resolves the request as
        `rejected` instead of raising.  Returns False if the slot died."""
        try:
            self._ensure_pages(i, int(self.seq_lens[i])
                               + (min(self.chunk, len(self.slots[i].req.prompt)
                                      - self.slots[i].prefill_pos)
                                  if self.slots[i].phase == "prefill" else 1))
            self._maybe_cow(i)
            return True
        except PoolExhausted as e:
            self._fail_slot(i, "rejected", detail=str(e))
            return False

    def step(self) -> list[tuple[int, int]]:
        """One scheduler iteration; returns (rid, token) pairs emitted.  A
        step that ran the device adds its engine.step phase reading to the
        step latencies (injected straggler sleeps included — that skew is
        exactly what the p99 is for)."""
        t0 = self._phase_s["step"]
        span = jax.profiler.StepTraceAnnotation("engine.step",
                                               step_num=self._step_idx)
        with self._phase("step", span):
            emitted = self._step(span)
        if emitted is None:
            return []
        self._step_lat_s.append(self._phase_s["step"] - t0)
        return emitted

    def _step(self, span):
        """step()'s phases, in order; None when no device step ran."""
        with self._phase("schedule"):
            # retire finished sequences (before expiry: a request that is
            # done resolves `completed` even if its deadline passed this
            # instant), then cancel expired work, then fill freed slots from
            # the queue; prefill steps go first
            for i, slot in enumerate(self.slots):
                if slot is not None and slot.done:
                    self._resolve(slot.req, "completed",
                                  generated=slot.generated)
                    self._free_slot(i)
            self._expire_deadlines()
            self._admit()
            self._maybe_poison_page()
            kind = "prefill"
            part = [i for i, s in enumerate(self.slots)
                    if s is not None and s.phase == "prefill"]
            if not part:
                kind = "decode"
                part = [i for i, s in enumerate(self.slots)
                        if s is not None and s.phase == "decode"
                        and not s.done]
        if not part:
            return None
        with self._phase("page_in"):
            # page in first: allocation may preempt a slot (even one in
            # `part`), so the batch is built only from survivors.
            # _maybe_cow runs after paging: a warm slot resuming mid-page
            # (fully cached page-aligned prompt) must write into a private
            # copy, never the shared page.
            for i in part:
                if self.slots[i] is not None:
                    self._page_in(i)
            part = [i for i in part if self.slots[i] is not None]
            width = self.chunk if kind == "prefill" else 1
            tokens = np.zeros((self.max_seqs, width), np.int32)
            num_new = np.zeros((self.max_seqs,), np.int32)
            for i in part:
                s = self.slots[i]
                if kind == "prefill":
                    seg = s.req.prompt[s.prefill_pos:s.prefill_pos + width]
                    tokens[i, :len(seg)] = seg
                    num_new[i] = len(seg)
                else:
                    tokens[i, 0] = s.next_token
                    num_new[i] = 1
        if not part:
            return None
        span.set_metadata(kind=kind, seqs=len(part))
        toks, bad = self._run_step(tokens, num_new, part)
        if toks is None:
            return None                  # step failed twice: slots resolved
        with self._phase("commit"):
            # whole-array reads: no Python call per slot, which a profiler
            # with its Python tracer on would record and the step would pay
            self.step_rows.append((kind, tuple(zip(
                num_new[part].tolist(), self.seq_lens[part].tolist()))))
            self.seq_lens += num_new
            if kind == "decode" and self._decode_windows:
                self._count_decode_blocks()
            self._reclaim_expired()
            emitted: list[tuple[int, int]] = []
            for i in part:
                s = self.slots[i]
                if kind == "prefill":
                    s.prefill_pos += int(num_new[i])
                if bad[i]:
                    # NaR reached this slot's logits: fail it before any
                    # token is emitted or any page registers in the prefix
                    # index (poisoned KV must never be shared)
                    self._fail_slot(i, "failed_nar",
                                    "NaR detected in output logits")
                    continue
                # decoding, or its prompt just done
                if kind == "decode" or s.phase == "decode":
                    self._keep_logits(i)
                    tok = int(toks[i])
                    s.generated.append(tok)
                    s.next_token = tok
                    emitted.append((s.req.rid, tok))
                self._register(i)
            self.counters[f"{kind}_steps"] += 1
        return emitted

    def run(self, requests=None, max_steps: int | None = None
            ) -> dict[int, np.ndarray]:
        """Drain: submit `requests` (iterable of (prompt, max_new)) and step
        until everything finished.  Returns {rid: generated tokens}."""
        if requests is not None:
            for prompt, max_new in requests:
                self.submit(prompt, max_new)
        steps = 0
        while self.waiting or self.active:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return dict(self.finished)
