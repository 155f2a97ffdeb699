"""Shared by the traffic kinds that drive a ``GenerationEngine`` in-process:
building it from a cell's sizes, the shape warm-up, snapshots of its
counters, and the greedy check against the plain reference.

A cell sets sizes only. Every other engine option is ``JaxGenConfig``'s
default, so a later PR that improves a default is measured as users feel it.
"""

import concurrent.futures
import time

import numpy as np

from benchmark import harness, lengths, reference

# sizes a traffic file may set under "engine"; anything else is refused
SIZE_KEYS = ("max_num_seqs", "max_model_len", "num_pages")
# --rehearse only: the program's CPU test geometry (tests/ use the same)
REHEARSE_ENGINE = {"dtype": "float32", "page_size": 16, "prefill_chunk": 16}


def build_engine(ctx, sizes):
    """Weights on the device in one jitted call from the seed, in the dtype
    they are served in; then the engine with the cell's sizes."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.transformer import init_params

    unknown = sorted(set(sizes) - set(SIZE_KEYS))
    if unknown:
        raise SystemExit(f"a cell sets sizes only; not sizes: {unknown}")
    extra = dict(REHEARSE_ENGINE) if ctx.rehearse else {}
    gcfg = JaxGenConfig(**sizes, **extra)
    dtype = jnp.float32 if ctx.rehearse else jnp.bfloat16
    cfg = ctx.model_config
    params = jax.jit(lambda key: init_params(cfg, key, dtype=dtype))(
        jax.random.PRNGKey(ctx.seed % (2 ** 31))
    )
    jax.block_until_ready(params)
    engine = GenerationEngine(gcfg, model_config=cfg, params=params)
    engine.start()
    harness.log(
        f"engine up: attn_impl={engine.attn_impl} slots={gcfg.max_num_seqs} "
        f"decode_chunk={gcfg.decode_chunk} pipeline={gcfg.decode_pipeline}"
    )
    return engine, params


def payload(input_ids, max_new_tokens, sampling, rid):
    return {
        "rid": rid,
        "input_ids": input_ids,
        "sampling_params": {"max_new_tokens": int(max_new_tokens), **sampling},
    }


def snapshot(engine):
    """The engine's counters, now."""
    return {
        "t": time.monotonic(),
        "counters": {
            k: float(v) for k, v in engine.metrics().items()
            if isinstance(v, (int, float))
        },
    }


def program_names(engine):
    """(phase, signature) of every program the engine has compiled or loaded:
    diagnostics only, to name a compile that landed inside the window."""
    tracker = getattr(engine, "compiles", None)
    return set(getattr(tracker, "signatures", {}) or {})


def delta(after, before):
    return {
        "window_s": after["t"] - before["t"],
        "counters": harness.counter_delta(
            after["counters"], before["counters"]
        ),
    }


def check_alive(engine):
    """The engine's loop thread dies with the exception of a failed dispatch
    (a program that does not fit, a compiler fault) and its futures then
    never resolve: fail at once instead of waiting out a timeout."""
    thread = getattr(engine, "_thread", None)
    if thread is not None and not thread.is_alive():
        raise RuntimeError("the engine's loop thread has died (see stderr)")


def wait_until(engine, cond, timeout, what):
    end = time.monotonic() + timeout
    while not cond():
        check_alive(engine)
        if time.monotonic() > end:
            raise RuntimeError(f"timed out after {timeout:.0f}s: {what}")
        time.sleep(0.002)


def wait_running(engine, n, timeout):
    """Until ``n`` requests hold slots and nothing is queued."""
    def ready():
        m = engine.metrics()
        return m["running_requests"] >= n and m["queued_requests"] == 0

    wait_until(engine, ready, timeout, f"{n} requests admitted")


def submit_one(engine, pay, timeout):
    """Submit and wait until the engine has admitted it (alone in its wave,
    when the caller sends nothing else meanwhile)."""
    before = engine.metrics()["total_requests"]
    fut = engine.submit(pay)
    wait_until(
        engine,
        lambda: fut.done() or engine.metrics()["total_requests"] > before,
        timeout, "one request admitted",
    )
    return fut


def result(engine, fut, timeout):
    end = time.monotonic() + timeout
    while True:
        try:
            return fut.result(timeout=1.0)
        except (TimeoutError, concurrent.futures.TimeoutError):
            check_alive(engine)
            if time.monotonic() > end:
                raise


def results(engine, futures, timeout):
    return [result(engine, f, timeout) for f in futures]


# ---------------------------------------------------------------- warm-up --
def warm_shapes(ctx, engine, prompt_pool, sampling, spec):
    """Touch the programs this cell's traffic uses, and no others.

    The engine's programs are keyed by: decode (row bucket, page-window
    bound); admission (unique prompts in the wave, suffix bucket, page window,
    cached-prefix bound); and a family of small eager programs keyed by
    (unique prompts, requests admitted in the wave). ``spec`` (the traffic
    file's "warmup") says which the window reaches:

    - ``filler_levels``: [n, ...]. For each n, n requests of one shared short
      prompt hold slots, so decode runs at that row bucket, while
    - ``sweep`` {prompt, to}: ONE long request starts at ``prompt`` tokens and
      generates until its context reaches ``to``, so decode runs at every
      page-window bound between them at that row bucket (the fillers grow at
      the same pace and stay far below it). The fillers are then aborted;
    - ``admission`` (first level only): {"unique": [1, 2, ...], "siblings":
      bool, "admitted_up_to": n}. For every page-class of the prompt law,
      waves of that many distinct prompts; with ``siblings`` also each wave
      again (now cached), one cached prompt beside one fresh, and waves of 2
      to ``admitted_up_to`` requests over 1 and over 2 distinct prompts;
    - ``check`` (first level only): the 4 requests of ``correct`` (a), one at
      a time, so they need no program of their own.

    What the window needs and this missed shows as a compile inside the
    window, which fails the run's ``correct``.
    """
    rng = np.random.default_rng(ctx.seed + 7)
    vocab = ctx.model_config.vocab_size
    q = engine.config.kv_bucket
    limit = ctx.limit_s
    new = spec.get("wave_new_tokens", 8)
    adm = spec.get("admission")
    levels = spec.get("filler_levels", [])
    checked, n_waves, n_sweeps = [], 0, 0

    def sweep():
        sw = spec.get("sweep")
        if not sw:
            return 0
        top = min(sw["to"], engine.config.max_model_len)
        result(engine, engine.submit(payload(
            lengths.tokens(rng, vocab, sw["prompt"]), top - sw["prompt"] - 1,
            sampling, "warm-sweep",
        )), limit)
        return 1

    def admission_waves():
        classes = {}
        for n in prompt_pool:  # largest prompt of each page-class
            k = -(-int(n) // q)
            classes[k] = max(classes.get(k, 0), int(n))
        reps = [classes[k] for k in sorted(classes)]
        prompts, count = {}, 0

        def wave(items):
            """items: (key, length); a key seen before is a sibling."""
            futs = []
            for key, n in items:
                if key not in prompts:
                    prompts[key] = lengths.tokens(rng, vocab, n)
                futs.append(engine.submit(payload(
                    prompts[key], new, sampling,
                    f"warm-wave-{len(prompts)}-{len(futs)}",
                )))
            results(engine, futs, limit)
            return 1

        for u in adm["unique"]:
            for i, a in enumerate(reps):
                # u distinct prompts, the longest of page-class i
                fresh = [(("w", u, i, k), a if k == 0 else reps[0])
                         for k in range(u)]
                count += wave(fresh)
                if not adm.get("siblings"):
                    continue
                count += wave(fresh)  # the same prompts, now cached
                if u >= 2:
                    for j, b in enumerate(reps):
                        # one cached prompt beside one fresh, both ways
                        count += wave([(("w", u, i, 0), a),
                                       (("m", u, i, j, 0), b)])
                        count += wave([(("m", u, i, j, 1), b),
                                       (("w", u, i, 0), a)])
        # more requests admitted in one wave than it has distinct prompts
        # (siblings): over one prompt, new and cached, and over two
        one, two = ("w", 1, 0, 0), ("w", 2, 0, 1)
        for n in range(2, int(adm.get("admitted_up_to", 1)) + 1):
            count += wave([(("n", n), reps[0])] * n)
            count += wave([(one, reps[0])] * n)
            count += wave([(one, reps[0])] * (n - 1) + [(two, reps[0])])
        return count

    def with_fillers(n_fill, body):
        shared = lengths.tokens(rng, vocab, spec.get("filler_prompt", 32))
        live = [
            engine.submit(payload(
                shared, spec["filler_new_tokens"], sampling, f"warm-fill-{i}"
            ))
            for i in range(n_fill)
        ]
        wait_running(engine, n_fill, limit)
        body()
        engine.pause()  # aborts the fillers
        results(engine, live, limit)
        engine.continue_generation()

    def first_level():
        nonlocal n_waves
        if adm:
            n_waves = admission_waves()
        if spec.get("check"):
            checked.extend(check_requests(ctx, engine, sampling))

    if not levels:
        first_level()
    for li, n_fill in enumerate(levels):
        def body(first=(li == 0)):
            nonlocal n_sweeps
            if first:
                first_level()
            n_sweeps += sweep()
        with_fillers(n_fill, body)
    harness.log(
        f"warm-up: filler levels {levels}, {n_waves} admission waves, "
        f"{n_sweeps} context sweeps, {len(checked)} check requests; "
        f"{ctx.watch.compiles} programs so far "
        f"({ctx.watch.cache_hits} from the cache, "
        f"{ctx.watch.seconds:.0f} s compiling)"
    )
    return checked


# ------------------------------------------------------------ correctness --
def check_requests(ctx, engine, sampling, n_seqs=4, total=128):
    """``correct`` (a) for a generation cell, the engine's half: 4 seeded
    requests of at most ``total`` tokens with the cell's own sampling, one at
    a time. Returns [(prompt, result)]; the engine's own token stream is then
    teacher-forced through the plain reference (``reference_compare``) once
    the engine is stopped, and the per-token logprobs must agree."""
    rng = np.random.default_rng(ctx.seed + 11)
    vocab = ctx.model_config.vocab_size
    if ctx.rehearse:
        total = min(total, 24)
    out = []
    for i, n in enumerate(rng.integers(total // 4, total // 2, size=n_seqs)):
        p = lengths.tokens(rng, vocab, int(n))
        res = result(engine, engine.submit(
            payload(p, total - len(p), sampling, f"check-{i}")
        ), ctx.limit_s)
        out.append((p, res))
    return out


def reference_compare(ctx, params, checked):
    system, ref = [], []
    for p, o in checked:
        seq = list(p) + list(o["output_ids"])
        want = reference.token_logprobs(ctx.reference_config, params, seq)
        ref.append(want[len(p) - 1:])
        system.append(np.asarray(o["output_logprobs"], np.float64))
    return harness.compare_logprobs(system, ref, ctx.logprob_limits)


def request_ok(res, max_new_tokens):
    """(b): exactly ``max_new_tokens`` tokens, every logprob finite."""
    lp = np.asarray(res["output_logprobs"], np.float64)
    return (
        len(res["output_ids"]) == max_new_tokens
        and len(lp) == max_new_tokens
        and bool(np.isfinite(lp).all())
        and res["meta_info"]["finish_reason"]["type"] == "length"
    )
