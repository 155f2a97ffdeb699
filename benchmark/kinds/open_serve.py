"""Open loop: requests arrive on a seeded schedule at a rate fixed in the
traffic file, whatever the engine does. Every request due in the window is
timed from when it was DUE (generator lateness + the engine's own ttft); a
request that failed, was refused or had not finished when the drain time ran
out is ranked above every measured value.
"""

import threading
import time

import numpy as np

from benchmark import genlib, harness, lengths


def schedule(p, vocab, seed, seconds, rate=None):
    """(due_s, prompt tokens, max_new_tokens) for every request of the
    window: the same lengths for every seed, in the seed's order."""
    rng = np.random.default_rng(seed)
    arrivals = dict(p["arrivals"])
    if rate is not None:
        arrivals["rate"] = rate
    due = lengths.due_times(arrivals, seconds, rng)
    n = len(due)
    plens = lengths.ordered(p["prompt_len"], n, rng)
    outs = lengths.ordered(p["output_len"], n, rng)
    return [
        (float(t), lengths.tokens(rng, vocab, int(a)), int(b))
        for t, a, b in zip(due, plens, outs)
    ]


def run(ctx):
    p = ctx.traffic
    engine, params = genlib.build_engine(ctx, p["engine"])
    try:
        checked = genlib.warm_shapes(
            ctx, engine, lengths.pool(p["prompt_len"], 64), p["sampling"],
            p["warmup"],
        )
        outcome = measure(ctx, engine, None)
    finally:
        engine.stop()
    del engine
    # (a): the reference runs once the engine is stopped and its pool freed
    cmp = genlib.reference_compare(ctx, params, checked)
    outcome.checks["reference_agrees"] = cmp["ok"]
    outcome.notes["reference"] = cmp
    return outcome


def measure(ctx, engine, rate):
    """One window at ``rate`` (None = the traffic file's)."""
    p = ctx.traffic
    plan = schedule(
        p, ctx.model_config.vocab_size, ctx.seed, ctx.seconds, rate
    )
    lock = threading.Lock()
    recs = []

    def on_done(fut, rec):
        rec["t_done"] = time.monotonic()
        err = fut.exception()
        if err is not None:
            rec["error"] = f"{type(err).__name__}: {err}"
        else:
            res = fut.result()
            meta = res["meta_info"]
            n = len(res["output_ids"])
            rec.update(
                ok=genlib.request_ok(res, rec["max_new"]),
                ttft=meta["ttft"], latency=meta["latency"], n_out=n,
            )
        with lock:
            rec["done"] = True

    compiles0, compiled0 = ctx.watch.compiles, ctx.watch.compiled
    programs0 = genlib.program_names(engine)
    ctx.mark_window_start()
    before = genlib.snapshot(engine)
    t0 = before["t"]
    traced = harness.TracedWindow(ctx.trace, ctx.trace_dir)
    trace_until = t0 + min(ctx.seconds, p.get("trace_seconds", ctx.seconds))
    mid = None
    with traced:
        i = 0
        for i, (due, prompt, max_new) in enumerate(plan):
            if traced.on and t0 + due > trace_until:
                break
            _send(engine, recs, on_done, t0, due, prompt, max_new,
                  p["sampling"], i)
            if mid is None and due >= ctx.seconds / 2:
                mid = engine.metrics()["queued_requests"]
        else:
            i = len(plan)
        if traced.on:
            with harness.span("wait"):
                time.sleep(max(0.0, trace_until - time.monotonic()))
    for j in range(i, len(plan)):  # a traced run's remaining requests
        due, prompt, max_new = plan[j]
        _send(engine, recs, on_done, t0, due, prompt, max_new,
              p["sampling"], j)
        if mid is None and due >= ctx.seconds / 2:
            mid = engine.metrics()["queued_requests"]
    time.sleep(max(0.0, t0 + ctx.seconds - time.monotonic()))
    genlib.check_alive(engine)
    after = genlib.snapshot(engine)
    compiles_in_window = ctx.watch.compiled - compiled0
    loads_in_window = ctx.watch.compiles - compiles0 - compiles_in_window
    new_programs = sorted(
        map(str, genlib.program_names(engine) - programs0)
    )[:12]
    queue_end = after["counters"]["queued_requests"]
    running_end = after["counters"]["running_requests"]
    # drain: what was due in the window gets this long to finish
    deadline = time.monotonic() + p["drain_s"]
    while time.monotonic() < deadline:
        with lock:
            if all(r.get("done") for r in recs):
                break
        time.sleep(0.01)
    cutoff = time.monotonic()
    engine.pause()  # what is still running is a miss; free its slots
    time.sleep(0.05)
    engine.continue_generation()

    requests, failed = [], 0
    for r in recs:
        late_ms = (r["t_sent"] - r["t_due"]) * 1000.0
        if r.get("ok"):
            n = r["n_out"]
            requests.append({
                "late_ms": late_ms,
                "ttft_ms": late_ms + r["ttft"] * 1000.0,
                "tpot_ms": (
                    (r["latency"] - r["ttft"]) * 1000.0 / (n - 1)
                    if n > 1 else None
                ),
            })
        else:
            failed += 1
            waited = (cutoff - r["t_due"]) * 1000.0
            requests.append({"miss": True, "late_ms": late_ms,
                             "ttft_ms": waited, "tpot_ms": waited})
    d = genlib.delta(after, before)
    obs = harness.Observation(
        window_s=d["window_s"], setup_s=ctx.setup_s, counters=d["counters"],
        histograms=d["histograms"], ledger=d["ledger"], requests=requests,
        values={"decode_chunk": float(engine.config.decode_chunk)},
        trace=traced.reduce(), peaks=ctx.peaks,
    )
    done_share = 1.0 - failed / max(1, len(recs))
    return harness.Outcome(
        obs=obs, attempted=len(recs), failed=failed,
        checks={
            "lengths_exact_and_finite": failed == 0 and len(recs) > 0,
            "no_compile_in_window": compiles_in_window == 0,
        },
        notes={
            "rate": rate if rate is not None else p["arrivals"]["rate"],
            "sent": len(recs), "finished_share": done_share,
            "queue_mid": mid, "queue_end": queue_end,
            "running_end": running_end,
            "compiles_in_window": compiles_in_window,
            "cache_loads_in_window": loads_in_window,
            "new_programs_in_window": new_programs,
            "errors": [r["error"] for r in recs if "error" in r][:3],
        },
    )


def _send(engine, recs, on_done, t0, due, prompt, max_new, sampling, i):
    wait = t0 + due - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    rec = {"t_due": t0 + due, "max_new": max_new}
    recs.append(rec)
    with harness.span("submit"):
        rec["t_sent"] = time.monotonic()
        fut = engine.submit(
            genlib.payload(prompt, max_new, sampling, f"q{i}")
        )
    fut.add_done_callback(lambda f, rec=rec: on_done(f, rec))
