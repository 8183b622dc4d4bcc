"""The serving cells: the program's PagedServingEngine driven by a backlog.

Set-up makes the weights from the seed (one jitted call: draw, then the
program's PTQ), builds the engine at the configuration's shape and warms
up the two programs the window drives: one prefill chunk and one decode
step.  The backlog is then submitted, and the window opens once the first
admission wave has prefilled; it drives `engine.step` under the
configuration's matmul precision, on the host clock.

After the window: the peak device memory is read, the configuration's
family (bench/families/) collects what its comparison takes from the live
engine, the program's state is freed, and the family's plain reference
(for the dense family, bench/reference.py) is run over a sample,
drawn from the seed, of the requests that were served tokens, with the one
served at the deepest position among them: each teacher-forced on its
prompt and the tokens it was served, finished or not.  The number compared
is the widest gap by which a served (greedy) token's logit lies below the
reference's best logit at that position.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import time

import numpy as np

from bench import traffic as T


class _Compiles:
    """Counts backend compilations while it is `on`."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.on, self.n = False, 0
        name = dispatch.BACKEND_COMPILE_EVENT

        def listen(event, duration, **kw):
            if self.on and event == name:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


class Rows:
    """Each step's (new tokens, tokens cached before) for every sequence it
    computed, kept from what the harness sees itself: the prompts it
    submitted (admitted first in, first out), the length of the engine's
    queue, its prefill and decode step counters, the prefill chunk and the
    tokens each step returns.  It follows cold prefills only: once a prefix
    hit, a preemption or a token it cannot place is seen, `sound` is False
    and no rows are given."""

    def __init__(self, chunk: int):
        self.chunk = chunk
        self.order: list[int] = []
        self.plen: dict[int, int] = {}
        self.pos: dict[int, int] = {}           # prompt tokens prefilled
        self.served: dict[int, int] = {}        # tokens given
        self.steps: list[list[tuple[int, int]]] = []
        self.sound = True

    def submit(self, rid: int, prompt_len: int):
        self.order.append(rid)
        self.plen[rid], self.pos[rid], self.served[rid] = prompt_len, 0, 0

    @staticmethod
    def counters(engine) -> tuple:
        """The engine's counters that `after_step` compares against."""
        c = engine.counters
        return (c["prefill_steps"], c["decode_steps"],
                c["prefix_hit_tokens"], c["preempted"])

    def after_step(self, engine, before: tuple, emitted):
        """Keep the rows of the step that ran since `before` was read, which
        gave the (rid, token) pairs `emitted`."""
        now = self.counters(engine)
        if now[2:] != before[2:]:
            self.sound = False
        rows = []
        if now[0] > before[0]:                        # a prefill step
            admitted = len(self.order) - len(engine.waiting)
            for rid in self.order[:admitted]:
                if self.served[rid] or rid in engine.outcomes:
                    continue
                new = min(self.chunk, self.plen[rid] - self.pos[rid])
                rows.append((new, self.pos[rid]))
                self.pos[rid] += new
            if any(self.pos[r] != self.plen[r] or self.served[r]
                   for r, _ in emitted):
                self.sound = False
        elif now[1] > before[1]:                      # a decode step
            for rid, _ in emitted:
                if not self.served[rid]:
                    self.sound = False
                rows.append((1, self.plen[rid] + self.served[rid] - 1))
        elif emitted:
            self.sound = False
        for rid, _ in emitted:
            self.served[rid] += 1
        self.steps.append(rows)


def _span(traced: bool, name: str):
    if traced:
        import jax
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


def _warm_up(engine, m, eng, rng):
    """Compile the prefill chunk and the decode step: a prompt of one chunk,
    two tokens served."""
    prompt = rng.integers(0, m["vocab"], eng["prefill_chunk"])
    engine.submit(prompt.astype(np.int32), 2)
    while engine.waiting or engine.active:
        engine.step()


def run(cell: dict, args, t_proc0: float, log) -> dict:
    import jax

    from bench import families, program
    conf, mix, m = cell["config"], cell["mix"], cell["model"]
    fam = families.load(conf)
    eng, check = cell["engine"], cell["check"]
    if eng["prefill_chunk"] % eng["page_size"]:
        raise SystemExit("bench: prefill_chunk must be a multiple of "
                         "page_size")
    traced = bool(args.trace)
    precision = args.precision or conf["matmul_precision"]
    prec_ctx = (contextlib.nullcontext() if precision == "default"
                else jax.default_matmul_precision(precision))
    compiles = _Compiles()

    marks = [time.perf_counter()]
    cfg = program.model_config(conf["arch"], m, fam)
    program.check_layout(cfg, m, fam)
    fb0 = program.fallback_count()
    with prec_ctx:
        params = fam.serving_params(args.seed, m, cfg)
        jax.block_until_ready(params)
        marks.append(time.perf_counter())
        engine = program.make_engine(params, cfg, eng, args.seed)
        marks.append(time.perf_counter())
        _warm_up(engine, m, eng, np.random.default_rng([args.seed, 7]))
    engine.reset_stats()
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t_proc0
    phases = zip(("start", "weights", "engine", "warm_up"),
                 [t_proc0] + marks[:-1], marks)
    log(f"setup_s={setup_s:.3f} " + " ".join(f"{k}={b - a:.3f}"
                                             for k, a, b in phases))

    reqs = T.backlog(mix, args.seed, m["vocab"], m["context"])
    book = Rows(eng["prefill_chunk"])
    prompt_of, toks = {}, {}
    step_spans = []
    ctr = engine.counters

    def one_step():
        """One engine step; returns (start, end, tokens given) or None
        when the engine has nothing left to do."""
        if not (engine.waiting or engine.active):
            return None
        before = book.counters(engine)
        with _span(traced, "bench.step"):
            t_a = time.perf_counter()
            emitted = engine.step()
            t_b = time.perf_counter()
        book.after_step(engine, before, emitted)
        for rid, tok in emitted:
            toks[rid].append(tok)
        return t_a, t_b, len(emitted)

    log_dir = None
    with prec_ctx:
        for r in reqs:
            with _span(traced, "bench.submit"):
                rid = engine.submit(r.prompt, r.max_new)
            prompt_of[rid], toks[rid] = r.prompt, []
            book.submit(rid, len(r.prompt))
        while ctr["decode_steps"] < 1 and one_step():
            pass
        if traced:
            out = os.path.join(program.ROOT, "bench_out")
            os.makedirs(out, exist_ok=True)
            log_dir = tempfile.mkdtemp(prefix="trace-", dir=out)
            jax.profiler.start_trace(log_dir)
        st0 = dict(ctr)
        n_rows0 = len(book.steps)
        served0 = {r: len(t) for r, t in toks.items()}
        tok_in_win = 0
        compiles.on = True
        t_open = t_close = time.perf_counter()
        with _span(traced, "bench.window"):
            while t_close < t_open + args.seconds:
                got = one_step()
                if got is None:
                    log("the backlog ran out inside the window")
                    break
                step_spans.append(got[:2])
                t_close = got[1]
                tok_in_win += got[2]
        compiles.on = False
        st1 = dict(ctr)
        n_rows1 = len(book.steps)
        if traced:
            jax.profiler.stop_trace()

    window_s = t_close - t_open
    win = {k: st1.get(k, 0) - st0.get(k, 0)
           for k in ("prefill_steps", "decode_steps", "prefix_hits")}
    peak = _peak_bytes()
    outcomes = {rid: engine.outcomes[rid].status for rid in engine.outcomes
                if rid in prompt_of}
    fallbacks = program.fallback_count() - fb0

    # the backlog was due before the window: the requests it answers for
    # are those it served a token, or resolved otherwise
    attempted_rids = [r for r in prompt_of
                      if len(toks[r]) > served0[r]
                      or outcomes.get(r, "completed") != "completed"]
    lost = [r for r in attempted_rids
            if outcomes.get(r, "completed") != "completed"]

    sample = _sample([r for r in prompt_of if toks[r]], prompt_of, toks,
                     check["sample_requests"], args.seed)
    seqs = [np.concatenate([prompt_of[r], np.asarray(toks[r], np.int32)])
            for r in sample]
    first = [len(prompt_of[r]) - 1 for r in sample]
    deepest = max((len(s) for s in seqs), default=0)
    spans_ms = [1e3 * (b - a) for a, b in step_spans]
    work_rows = book.steps[n_rows0:n_rows1] if book.sound else None
    collected = fam.collect(engine, sample)

    # free the program's state before the reference runs
    del engine, params
    engine = params = None
    program.free_device_memory()

    t_ref = time.perf_counter()
    gaps, _, bad = fam.teacher_forced(m, args.seed, seqs, first,
                                      m["context"], collected=collected)
    ref_s = time.perf_counter() - t_ref
    per_req = [float(g.max()) if len(g) else float("nan") for g in gaps]
    worst = max((g for g in per_req if g == g), default=float("nan"))
    limit = check["max_logit_gap"]
    mismatched = [r for r, g in zip(sample, per_req) if not g <= limit]
    n_served = int(sum(len(g) for g in gaps))
    log(f"window_s={window_s:.3f} tokens_in_window={tok_in_win} "
        f"attempted={len(attempted_rids)} lost={len(lost)} "
        f"sampled={len(sample)} sampled_tokens={n_served} "
        f"deepest_position={deepest} reference_s={ref_s:.2f} "
        f"compiles_in_window={compiles.n} precision={precision} "
        f"steps_window={win['prefill_steps']}p+{win['decode_steps']}d "
        f"prefix_hits={win['prefix_hits']} work_rows="
        f"{'kept' if work_rows is not None else 'not sound'} "
        f"peak_bytes={peak}")

    checks = {
        "max_logit_gap": (worst, limit, "max"),
        "sampled_tokens": (n_served, 1, "min"),
        "posit_domain_misses": (bad, 0, "max"),
        "kernel_fallbacks": (fallbacks, 0, "max"),
        "compiles_in_window": (compiles.n, 0, "max"),
    }
    extra = fam.extra_checks(m, check, collected)
    if set(extra) & set(checks):
        raise SystemExit(f"bench: the family's checks {sorted(extra)} "
                         f"reuse a common name")
    checks.update(extra)
    return {
        "setup_s": setup_s, "window_s": window_s, "checks": checks,
        "attempted": len(attempted_rids),
        # a sampled request outside the ones answered for still makes the
        # run incorrect, through the compared number
        "failed": len(set(lost) | (set(mismatched) & set(attempted_rids))),
        "peak_bytes": peak, "log_dir": log_dir, "per_request_gaps": per_req,
        "reads": {"tokens_in_window": tok_in_win, "step_ms": spans_ms,
                  "work_rows": work_rows, "model": m,
                  "collected": collected},
    }


def _peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def _sample(served, prompt_of, toks, n, seed):
    """n requests that were served tokens, drawn from the seed, with the one
    served at the deepest position among them."""
    if not served:
        return []
    size = {r: len(prompt_of[r]) + len(toks[r]) for r in served}
    deepest = max(sorted(served), key=lambda r: size[r])
    rest = sorted(r for r in served if r != deepest)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) \
        if rest and n > 1 else []
    return [deepest] + [int(r) for r in pick]
