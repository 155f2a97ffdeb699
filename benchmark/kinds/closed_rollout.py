"""Closed loop: a fixed number of requests in flight; a finished request is
replaced at once by the next of the seeded stream (what ``WorkflowExecutor``
does under ``max_concurrent_rollouts``). Consecutive requests of the stream
share a prompt in groups (GRPO samples of one prompt), so siblings meet the
prefix cache as in a real rollout.

The window starts in steady state: the first ``in_flight`` requests are drawn
already in progress (a length from the length-biased law, a progress fraction;
what was "already produced" is sent as prompt), and are admitted during
set-up.
"""

import queue
import threading
import time

import numpy as np

from benchmark import arith, genlib, harness, lengths


class Stream:
    """The seeded request stream: output lengths are the law's pool in the
    seed's order, again and again; each prompt serves ``group`` requests."""

    def __init__(self, p, vocab, seed):
        self.p, self.vocab = p, vocab
        self.rng = np.random.default_rng(seed)
        self.out_pool = lengths.pool(p["output_len"], p["pool"])
        self.prompt_pool = lengths.pool(
            p["prompt_len"], max(1, p["pool"] // p["group_size"])
        )
        self._outs, self._prompt_lens = [], []
        self._prompt, self._left = None, 0
        self.n = 0

    def _next_prompt(self):
        if self._left == 0:
            if not self._prompt_lens:
                self._prompt_lens = list(self.rng.permutation(self.prompt_pool))
            n = int(self._prompt_lens.pop())
            self._prompt = lengths.tokens(self.rng, self.vocab, n)
            self._left = self.p["group_size"]
        self._left -= 1
        return self._prompt

    def next(self):
        if not self._outs:
            self._outs = list(self.rng.permutation(self.out_pool))
        self.n += 1
        return self._next_prompt(), int(self._outs.pop())

    def in_progress(self, fill):
        """The slots as found at a random instant. ``fill`` is the traffic
        file's "stationary_fill": {"count", "long_count", "long_context",
        "anchor_to"}. The requests in flight are a length-biased sample of
        the output law (a request twice as long is twice as likely to be the
        one in flight). The ``long_count`` longest are found at
        ``long_context`` tokens of context, each with a prompt of its own,
        and what they had produced is sent as part of the prompt; the first
        of them, the anchor, runs until its context is ``anchor_to``. The
        rest have produced nothing yet and share prompts in groups like the
        stream. One long context, so the fill needs one prefill program and
        not one per page. Returns (long, groups): [(prompt, tokens to come)]
        and [[(prompt, tokens to come), ...] per shared prompt]."""
        n, n_long = fill["count"], fill["long_count"]
        total = np.sort(lengths.length_biased(self.out_pool, n))[::-1]
        ctx_len = fill["long_context"]
        plens = self.rng.permutation(np.resize(self.prompt_pool, n_long))
        long = []
        for i in range(n_long):
            done = ctx_len - int(plens[i])
            left = int(total[i]) - done
            if i == 0:
                left = fill["anchor_to"] - ctx_len
            if left < 1:
                raise ValueError(
                    f"stationary_fill: a context of {ctx_len} needs an "
                    f"output over {done} tokens, the sample has {total[i]}"
                )
            long.append((lengths.tokens(self.rng, self.vocab, ctx_len), left))
        rest = self.rng.permutation(total[n_long:])
        groups, g = [], self.p["group_size"]
        for k in range(0, len(rest), g):
            self._left = 0  # a new prompt for each group
            groups.append([
                (self._next_prompt(), int(length)) for length in rest[k:k + g]
            ])
        self._left = 0
        self.n += n
        return long, groups


def run(ctx):
    p = ctx.traffic
    engine, params = genlib.build_engine(ctx, p["engine"])
    sampling = p["sampling"]
    stream = Stream(p, ctx.model_config.vocab_size, ctx.seed)
    try:
        outcome, checked = _measure(ctx, engine, stream, sampling)
    finally:
        engine.stop()
    del engine
    # (a): the reference runs once the engine is stopped and its pool freed
    cmp = genlib.reference_compare(ctx, params, checked)
    outcome.checks["reference_agrees"] = cmp["ok"]
    outcome.notes["reference"] = cmp
    return outcome


def _measure(ctx, engine, stream, sampling):
    p = ctx.traffic
    n_flight = p["in_flight"]
    lock = threading.Lock()
    records, open_reqs = [], {}
    returned = queue.Queue()
    stop, filling, held = threading.Event(), threading.Event(), []
    filling.set()

    def submit(prompt, max_new, alone=False):
        rid = f"r{stream.n}-{time.monotonic_ns()}"
        rec = {"prompt_len": len(prompt), "max_new": max_new,
               "t_submit": time.monotonic(), "rid": rid}
        with lock:
            open_reqs[rid] = rec
        pay = genlib.payload(prompt, max_new, sampling, rid)
        fut = (genlib.submit_one(engine, pay, ctx.limit_s) if alone
               else engine.submit(pay))
        # the callback runs on the engine's loop thread: it only hands over
        fut.add_done_callback(
            lambda f, rec=rec: returned.put((f, rec, time.monotonic()))
        )

    def feeder():
        """Books each returned request and sends its replacement."""
        while True:
            item = returned.get()
            if item is None:
                return
            fut, rec, rec["t_done"] = item
            err = fut.exception()
            if err is not None:
                rec["error"] = f"{type(err).__name__}: {err}"
            else:
                res = fut.result()
                meta = res["meta_info"]
                rec.update(
                    n_out=len(res["output_ids"]), ttft=meta["ttft"],
                    latency=meta["latency"],
                    reason=meta["finish_reason"]["type"],
                    preemptions=meta["preemptions"],
                    ok=genlib.request_ok(res, rec["max_new"]),
                )
            with lock:
                open_reqs.pop(rec["rid"], None)
                records.append(rec)
            if stop.is_set():
                continue
            if filling.is_set():
                held.append(1)  # none finishes before the fill is done
                continue
            with harness.span("submit"):
                submit(*stream.next())

    feed = threading.Thread(target=feeder, name="bench-feeder", daemon=True)
    feed.start()
    # the stationary start, admitted in set-up. Long contexts go in pairs
    # (a wave of 8 prompts near max_model_len is a program the chip cannot
    # build or hold: PERF.md), the anchor among the first pair, so the page
    # window is long from the first decode chunk; then every request that
    # has produced nothing, in one wave, which takes the row bucket to where
    # the window runs
    fill = p["stationary_fill"]
    long, groups = stream.in_progress(fill)

    def admit(wave):
        before = engine.metrics()["total_requests"]
        for prompt, max_new in wave:
            submit(prompt, max_new)
        genlib.wait_until(
            engine,
            lambda: engine.metrics()["total_requests"] >= before + len(wave),
            ctx.limit_s, "a wave of the stationary fill admitted",
        )

    admit(long[:2])
    with lock:
        anchor = min(open_reqs.values(), key=lambda r: r["t_submit"])
    admit([r for grp in groups for r in grp])
    for k in range(2, len(long), 2):
        admit(long[k:k + 2])
    # burn-in: the closed loop itself runs from here (finished requests are
    # replaced), while set-up sends the admission shapes a window can meet,
    # the requests of ``correct`` (a), and then fills the last slots; it
    # lasts until the anchor has run to the end of the context, so decode
    # has run at every page-window bound up to max_model_len
    filling.clear()
    for _ in range(len(held)):
        submit(*stream.next())
    checked = genlib.warm_shapes(
        ctx, engine, stream.prompt_pool, sampling, p["warmup"]
    )
    for _ in range(n_flight - fill["count"]):
        submit(*stream.next(), alone=True)
    genlib.wait_until(
        engine, lambda: "t_done" in anchor, ctx.limit_s,
        "the anchor request ran to the end of the context",
    )
    genlib.check_alive(engine)
    time.sleep(p.get("settle_s", 1.0))

    compile_mark = ctx.watch.mark()
    programs0 = genlib.program_names(engine)
    ctx.mark_window_start()
    before = genlib.snapshot(engine)
    traced = harness.TracedWindow(ctx.trace, ctx.trace_dir)
    t_end = before["t"] + ctx.seconds
    with traced:
        t_trace_end = before["t"] + min(
            ctx.seconds, p.get("trace_seconds", ctx.seconds)
        )
        with harness.span("wait"):
            time.sleep(max(0.0, t_trace_end - time.monotonic()))
    time.sleep(max(0.0, t_end - time.monotonic()))
    genlib.check_alive(engine)
    after = genlib.snapshot(engine)
    compiles_in_window, loads_in_window = ctx.watch.since(compile_mark)
    new_programs = sorted(
        map(str, genlib.program_names(engine) - programs0)
    )[:12]
    stop.set()
    # abort what is in flight (each returns what it has); what was still
    # queued is admitted on resume and aborted by the next round
    for _ in range(50):
        engine.pause()
        time.sleep(0.05)
        while not returned.empty():
            time.sleep(0.01)
        engine.continue_generation()
        with lock:
            if not open_reqs:
                break
        time.sleep(0.1)
    returned.put(None)
    feed.join(timeout=30)
    d = genlib.delta(after, before)
    t0, t1 = before["t"], after["t"]
    with lock:
        recs = list(records)
    finished = [r for r in recs if r.get("reason") == "length"
                and t0 <= r["t_done"] <= t1]
    aborted = [r for r in recs if r.get("reason") == "abort"]
    errored = [r for r in recs if "error" in r] + list(open_reqs.values())
    bad = [r for r in finished if not r["ok"]]
    # live context per decode step, from what the benchmark tracked itself
    spans = [
        (r["prompt_len"], r["t_submit"] + r["ttft"],
         r["t_submit"] + r["latency"], r["n_out"])
        for r in recs if "ttft" in r
    ]
    obs = harness.Observation(
        window_s=d["window_s"], setup_s=ctx.setup_s,
        counters=d["counters"],
        values={
            "decode_chunk": float(engine.config.decode_chunk),
        },
        trace=traced.reduce(), peaks=ctx.peaks,
    )
    if traced.on and traced.host_s:
        # what the traced steps had to read: live contexts x bytes per token
        obs.values["kv_bytes_read_traced"] = (
            arith.kv_tokens_read(spans, t0, t0 + traced.host_s)
            * arith.kv_bytes_per_token(ctx.reference_config)
        )
    outcome = harness.Outcome(
        obs=obs,
        attempted=len(finished) + len(aborted) + len(errored),
        failed=len(bad) + len(errored),
        checks={
            "lengths_exact_and_finite": not bad and not errored,
            "generated_in_window":
                d["counters"].get("total_generated_tokens", 0) > 0,
            "no_compile_in_window": compiles_in_window == 0,
        },
        notes={
            "finished": len(finished), "in_flight_at_end": len(aborted),
            "compiles_in_window": compiles_in_window,
            "cache_loads_in_window": loads_in_window,
            "new_programs_in_window": new_programs,
            "errors": [r.get("error", "never returned") for r in errored][:3],
        },
    )
    return outcome, checked
