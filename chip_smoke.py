"""chip_smoke.py — does the system still start on the chip?

    python chip_smoke.py              # one TPU chip: grpo, parity, server
    python chip_smoke.py --chips 4    # one 4-chip host: the sharded trainer only
    python chip_smoke.py --rehearse   # the same phases, tiny model, on the CPU

The orchestrator (this process, when run without ``--child``) never imports
jax: a chip belongs to one process at a time, so every phase is a child
process, and the next starts only after the last one exited.

Phases at Qwen2-0.5B, published width (vocab 151936), random weights from
``--seed``:

- ``grpo``    3 steps of ``examples/gsm8k_grpo.main`` colocated (trainer and
              generation engine share the chip), bf16 compute / f32 params,
              Adam, the recipe's ``attn_impl: auto`` (the splash kernel on
              the chip), server defaults;
- ``parity``  the same greedy generations from an engine on the Pallas
              paged-decode kernel and one on the jnp gather;
- ``server``  ``python -m areal_tpu.inference.server`` over HTTP, started
              twice: the second start must find the first one's programs in
              the persistent compile cache;
- ``fsdp4``   (``--chips 4`` only) one PPO update on one device against the
              same on an ``fsdp=4`` mesh, then one Qwen2-1.5B step on it.

Without ``--rehearse`` a phase that does not see platform ``tpu`` fails; there
is no CPU fallback. The last line of stdout is exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# the whole run must end inside the driver's 1200 s, compilation included
TOTAL_LIMIT_S = 1140
PHASE_LIMIT_S = {"grpo": 800, "parity": 400, "server": 400, "fsdp4": 1500}
GRPO_STEPS = 3
# kernel vs jnp, bf16: the op is held to the tolerance the repo's own
# interpret-mode test uses (tests/test_paged_kernel_parity.py, rtol = atol =
# 3e-2); through 24 layers and a bf16 LM head the greedy logprobs are held to
# 2e-2 on the mean and to two bf16 ulps of a logit in [4, 8) on the worst one
PARITY_OP_TOL = 3e-2
PARITY_LOGPROB_MEAN_TOL = 2e-2
PARITY_LOGPROB_MAX_TOL = 2 * 2.0 ** -5
FSDP_REL_TOL = 2e-2
FSDP_SHARE_MAX = 0.3


# ---------------------------------------------------------------------------
# orchestrator (no jax here)
# ---------------------------------------------------------------------------
def _child_env(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    env.setdefault("TOKENIZERS_PARALLELISM", "false")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()
    return env


def _run_child(phase, args, limit):
    """One phase as its own process; returns its result line (a dict)."""
    log_path = os.path.join(args.out, f"{phase}.log")
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child", phase,
        "--seed", str(args.seed), "--out", args.out,
        "--chips", str(args.chips),
    ] + (["--rehearse"] if args.rehearse else [])
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=log, env=_child_env(args),
            text=True, start_new_session=True, cwd=ROOT,
        )
        timed_out = False
        try:
            out, _ = proc.communicate(timeout=limit)
        except subprocess.TimeoutExpired:
            timed_out = True
        finally:
            _kill_group(proc)  # nothing a phase started outlives it
        if timed_out:
            out, _ = proc.communicate()
        log.write(out or "")
    if timed_out:
        return {"phase": phase, "ok": False,
                "error": f"timed out after {limit:.0f} s"}
    result = None
    for line in (out or "").splitlines():
        if line.startswith('{"phase"'):
            result = json.loads(line)
    if result is None:
        result = {"phase": phase, "ok": False,
                  "error": f"no result line (exit code {proc.returncode})"}
    elif proc.returncode != 0:
        result["ok"] = False
        result.setdefault("error", f"exit code {proc.returncode}")
    return result


def _kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _tail(path, n=60):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def orchestrate(args):
    phases = ["fsdp4"] if args.chips == 4 else ["grpo", "parity", "server"]
    if args.phase:
        unknown = [p for p in args.phase if p not in phases]
        if unknown:
            raise SystemExit(f"--phase {unknown}: this mode runs {phases}")
        phases = [p for p in phases if p in args.phase]
    want_platform = "cpu" if args.rehearse else "tpu"
    deadline = time.monotonic() + (
        PHASE_LIMIT_S["fsdp4"] if args.chips == 4 else TOTAL_LIMIT_S
    )
    ok, device, results = True, None, []
    if not os.path.isdir(os.path.join(ROOT, "areal_tpu")):
        print(f"chip_smoke: no areal_tpu package beside {__file__}",
              file=sys.stderr)
        ok, phases = False, []
    else:
        os.makedirs(args.out, exist_ok=True)
    for phase in phases:
        limit = min(PHASE_LIMIT_S[phase], deadline - time.monotonic())
        t0 = time.monotonic()
        if limit <= 0:
            res = {"phase": phase, "ok": False, "error": "no time left"}
        elif phase == "server":
            res = phase_server(args, limit)
        else:
            res = _run_child(phase, args, limit)
        res["seconds"] = round(time.monotonic() - t0, 1)
        dev = res.get("device") or {}
        if res["ok"] and (
            dev.get("platform") != want_platform
            or (not args.rehearse and dev.get("count") != args.chips)
        ):
            res["ok"] = False
            res["error"] = (
                f"ran on {dev}: wanted {args.chips} x {want_platform}"
            )
        device = device or res.get("device")
        results.append(res)
        print(json.dumps(res), flush=True)
        if not res["ok"]:
            ok = False
            sys.stderr.write(
                f"--- {phase} failed; end of its log in {args.out} ---\n"
                + _tail(os.path.join(args.out, f"{phase}.log"))
            )
            break  # a later phase would only hide the first failure
    print(json.dumps({
        "compile": {
            r["phase"]: r.get("compile") for r in results if "compile" in r
        }
    }), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# children (each owns the chip for its lifetime)
# ---------------------------------------------------------------------------
def _result(phase, ok, device, **fields):
    return {"phase": phase, "ok": bool(ok), "device": device, **fields}


class _CompileWatch:
    """Backend compiles, their seconds, and persistent-cache hits of this
    process (jax.monitoring; a hit still fires a backend-compile event, so
    ``compiles - cache_hits`` is what XLA really compiled)."""

    def __init__(self):
        from jax import monitoring

        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def report(self, cache_dir):
        return {
            "backend_compiles": self.compiles,
            "compile_seconds": round(self.seconds, 1),
            "cache_hits": self.cache_hits,
            "cache_dir": cache_dir,
            "cache_entries": len(os.listdir(cache_dir)),
        }


def _model_config(args, which="qwen2_0p5b"):
    """Published widths (parallel/feasibility.py), or tiny when rehearsing."""
    if args.rehearse:
        from areal_tpu.models.config import tiny_config

        return tiny_config("qwen2")
    from areal_tpu.parallel.feasibility import MODEL_CONFIGS

    return MODEL_CONFIGS[which]()


def _fabricate(args, cfg):
    """Checkpoint, tokenizer and data under the output directory, made from
    the seed (the chip's machine has no network). Reused if already there."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hf_io
    from areal_tpu.models.transformer import init_params
    from tests.fixtures import make_gsm8k_jsonl, make_tiny_tokenizer

    model_dir = os.path.join(args.out, "model")
    tok_dir = os.path.join(args.out, "tok")
    data = os.path.join(args.out, "data", "train.jsonl")
    if not os.path.exists(os.path.join(model_dir, "model.safetensors")):
        params = init_params(
            cfg, jax.random.PRNGKey(args.seed), dtype=jnp.bfloat16
        )
        hf_io.save_params(params, cfg, model_dir)
        del params
    if not os.path.isdir(tok_dir):
        # every id the model can emit must decode
        make_tiny_tokenizer(tok_dir, vocab_size=cfg.vocab_size)
    if not os.path.exists(data):
        make_gsm8k_jsonl(data, n=64, seed=args.seed)
    return model_dir, tok_dir, data


def child_grpo(args, device, watch):
    import math

    import examples.gsm8k_grpo as example
    from areal_tpu.utils import compile_cache

    # Random weights never solve a problem: every real reward would be 0,
    # every group-normalised advantage 0 and every gradient exactly 0 — a
    # run that a broken loss passes. So the smoke scores something the
    # samples of a group differ in; the GRPO settings stay the yaml's.
    real_reward = example.gsm8k_reward_fn

    def smoke_reward(prompt, completion, prompt_ids, completion_ids, **kw):
        solved = real_reward(
            prompt, completion, prompt_ids, completion_ids, **kw
        )
        return float(solved) or float(sum(completion_ids) % 2)

    example.gsm8k_reward_fn = smoke_reward
    grpo_main = example.main
    cfg = _model_config(args)
    model_dir, tok_dir, data = _fabricate(args, cfg)
    fileroot = os.path.join(args.out, "grpo")
    # the recipe's own attn_impl, "auto": the splash kernel on the chip,
    # the XLA form in the rehearsal (checked below, from what main resolved)
    new_tokens = 8 if args.rehearse else 128
    argv = [
        "--config", os.path.join(ROOT, "examples", "gsm8k_grpo.yaml"),
        "experiment_name=chip-smoke", "trial_name=grpo",
        f"seed={args.seed}",
        f"cluster.fileroot={fileroot}",
        f"cluster.name_resolve.nfs_record_root={fileroot}/name_resolve",
        f"tokenizer_path={tok_dir}",
        f"actor.path={model_dir}",
        f"train_dataset.path={data}",
        "async_training=true",
        f"total_train_steps={GRPO_STEPS}",
        "train_dataset.batch_size=4",
        "rollout.consumer_batch_size=4",
        "gconfig.n_samples=4",
        f"gconfig.max_new_tokens={new_tokens}",
        f"actor.max_new_tokens={new_tokens}",
        "actor.dtype=bfloat16", "actor.param_dtype=float32",
        "actor.mb_spec.max_tokens_per_mb=4096",
        "server.max_num_seqs=16", "server.max_model_len=1024",
        "recover.mode=disabled", "saver.freq_steps=null",
    ]
    summary = grpo_main(argv)

    stats_file = os.path.join(fileroot, "chip-smoke", "grpo", "stats.jsonl")
    with open(stats_file) as f:
        lines = [json.loads(l) for l in f]
    checks = {
        "steps": len(lines) == GRPO_STEPS,
        "updates_successful": all(
            r.get("ppo_actor/update_successful") == 1.0 for r in lines
        ),
        "grad_norm_finite": all(
            math.isfinite(r.get("ppo_actor/grad_norm", math.nan))
            for r in lines
        ),
        "grad_norm_positive": all(
            r.get("ppo_actor/grad_norm", 0.0) > 0.0 for r in lines
        ),
        "version_advanced": summary["model_version"] == GRPO_STEPS,
        "vocab": cfg.vocab_size == (128 if args.rehearse else 151936),
    }
    if not args.rehearse:
        checks["server_attn_is_kernel"] = (
            summary["server_attn_impl"] == "kernel"
        )
        checks["actor_attn_is_flash"] = summary["actor_attn_impl"] == "flash"
        checks["splash_edge_ge_1024"] = (summary["splash_block"] or 0) >= 1024
    return _result(
        "grpo", all(checks.values()), device, checks=checks,
        vocab_size=cfg.vocab_size, num_layers=cfg.num_layers,
        actor_attn_impl=summary["actor_attn_impl"],
        server_attn_impl=summary["server_attn_impl"],
        splash_block=summary["splash_block"],
        model_version=summary["model_version"],
        grad_norm=[r.get("ppo_actor/grad_norm") for r in lines],
        n_tokens=[r.get("ppo_actor/n_tokens") for r in lines],
        reward_mean=[r.get("reward/mean") for r in lines],
        compile=watch.report(compile_cache.enabled_dir()),
    )


def _kernel_vs_jnp_op(cfg, gen, layout, seed, interpret):
    """The op alone on unit-normal q/k/v (a peaky softmax: one position
    masked wrongly moves the output by O(1)), at the model's head geometry
    and the config's page/block defaults: ragged lengths with an empty slot,
    page-exact ones and a full window, plus the in-flight chunk buffer."""
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(seed)
    d, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    page, pps, layers, chunk_t = gen.page_size, 4, 2, 32
    lengths = [0, 1, page - 1, page, page + 1, 2 * page, 3 * page + 7,
               pps * page, 5, page + page // 6, page // 3, 2 * page + 1,
               page // 4, 3 * page - 1, 2, 2 * page + page // 2]
    counts = [3, 0, 32, 1, 5, 0, 2, 7, 0, 31, 8, 0, 1, 16, 0, 4]
    rows, num_pages = len(lengths), len(lengths) * pps
    merged = layout == "head_merged"
    shape = pa.packed_pool_shape(layers, hkv, num_pages, page, d, merged)

    def pool():
        tok = rng.standard_normal((layers, hkv, num_pages, page, d))
        if merged:  # row = tokens x all kv heads (inverse of unpacked_view)
            tok = tok.transpose(0, 2, 3, 1, 4)
        return jnp.asarray(tok.reshape(shape), jnp.bfloat16)

    k_pages, v_pages = pool(), pool()
    tables = jnp.asarray(
        rng.permutation(num_pages).reshape(rows, pps), jnp.int32
    )
    q = jnp.asarray(rng.standard_normal((rows, hq, d)), jnp.bfloat16)
    ck, cv = (
        jnp.asarray(rng.standard_normal((rows, hkv, chunk_t, d)), jnp.bfloat16)
        for _ in range(2)
    )
    common = (q, k_pages, v_pages, jnp.int32(1), jnp.asarray(lengths, jnp.int32),
              tables, ck, cv, jnp.asarray(counts, jnp.int32))
    got = pa.paged_decode_attention(
        *common, pages_per_compute_block=gen.pages_per_compute_block,
        slots_per_block=gen.slots_per_block, num_kv_heads=hkv,
        interpret=interpret,
    )
    want = pa.paged_decode_attention_jnp(*common, num_kv_heads=hkv)
    # a slot with nothing to attend to is undefined (the engine never
    # reads it)
    defined = (np.asarray(lengths) > 0) | (np.asarray(counts) > 0)
    got = np.asarray(got, np.float32)[defined]
    want = np.asarray(want, np.float32)[defined]
    excess = np.abs(got - want) - PARITY_OP_TOL * np.abs(want)
    return {
        "max_abs_diff": float(np.abs(got - want).max()),
        "within_tolerance": bool(
            np.isfinite(got).all() and excess.max() <= PARITY_OP_TOL
        ),
    }


def child_parity(args, device, watch):
    """The Pallas paged-decode kernel against the jnp gather: the op alone,
    then through the engine — same weights, same greedy requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference import model_runner
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.transformer import init_params
    from areal_tpu.utils import compile_cache

    cfg = _model_config(args)
    if args.rehearse:
        # no chip: the same kernel runs in the Pallas interpreter
        import functools

        model_runner.paged_decode_attention = functools.partial(
            model_runner.paged_decode_attention, interpret=True
        )
    op = {
        layout: _kernel_vs_jnp_op(
            cfg, JaxGenConfig(page_size=32) if args.rehearse
            else JaxGenConfig(), layout, args.seed, args.rehearse,
        )
        for layout in ("token_packed", "head_merged")
    }
    dtype = "float32" if args.rehearse else "bfloat16"
    params = init_params(
        cfg, jax.random.PRNGKey(0),
        dtype=jnp.float32 if args.rehearse else jnp.bfloat16,
    )
    rng = np.random.default_rng(args.seed)
    n_prompts, new_tokens = 8, (8 if args.rehearse else 64)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
        for n in rng.integers(8, 48 if args.rehearse else 200, size=n_prompts)
    ]

    def run(attn_impl):
        gcfg = JaxGenConfig(
            dtype=dtype, max_num_seqs=8, attn_impl=attn_impl,
            max_model_len=128 if args.rehearse else 512,
            **(dict(page_size=16, prefill_chunk=16) if args.rehearse else {}),
        )
        engine = GenerationEngine(gcfg, model_config=cfg, params=params)
        assert engine.attn_impl == attn_impl
        engine.start()
        try:
            futs = [
                engine.submit({
                    "input_ids": p,
                    "sampling_params": {
                        "max_new_tokens": new_tokens, "greedy": True,
                    },
                })
                for p in prompts
            ]
            return [f.result(timeout=PHASE_LIMIT_S["parity"]) for f in futs]
        finally:
            engine.stop()

    kernel, ref = run("kernel"), run("jnp")
    # Random weights make a near-flat distribution over 151936 tokens, so
    # greedy ties split the streams early; what is comparable is the common
    # prefix: same history, same token, two attention implementations.
    prefix_lens, diffs, split_gaps = [], [], []
    for a, b in zip(kernel, ref):
        ta, tb = a["output_ids"], b["output_ids"]
        n = next(
            (i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
            min(len(ta), len(tb)),
        )
        prefix_lens.append(n)
        la = np.asarray(a["output_logprobs"], np.float64)
        lb = np.asarray(b["output_logprobs"], np.float64)
        diffs.extend(np.abs(la[:n] - lb[:n]).tolist())
        if n < min(len(ta), len(tb)):
            # where they split each side took its own argmax: a tie shows
            # as two near-equal logprobs (reported, not held to a limit)
            split_gaps.append(float(abs(la[n] - lb[n])))
    finite = all(
        np.all(np.isfinite(r["output_logprobs"])) for r in kernel + ref
    )
    worst, mean = float(np.max(diffs)), float(np.mean(diffs))
    checks = {
        "op_token_packed": op["token_packed"]["within_tolerance"],
        "op_head_merged": op["head_merged"]["within_tolerance"],
        "all_answered": all(
            len(r["output_ids"]) == new_tokens for r in kernel + ref
        ),
        "finite": finite,
        "first_token_equal": min(prefix_lens) >= 1,
        "logprobs_agree_mean": mean <= PARITY_LOGPROB_MEAN_TOL,
        "logprobs_agree_max": worst <= PARITY_LOGPROB_MAX_TOL,
    }
    return _result(
        "parity", all(checks.values()), device, checks=checks, op=op,
        op_tolerance=PARITY_OP_TOL, prompts=n_prompts, new_tokens=new_tokens,
        streams_equal=sum(n == new_tokens for n in prefix_lens),
        common_prefix_lens=prefix_lens, compared_logprobs=len(diffs),
        mean_abs_logprob_diff=mean, max_abs_logprob_diff=worst,
        mean_tolerance=PARITY_LOGPROB_MEAN_TOL,
        max_tolerance=PARITY_LOGPROB_MAX_TOL,
        logprob_gap_at_split=split_gaps,
        compile=watch.report(compile_cache.enabled_dir()),
    )


def _http(url, payload=None, timeout=300):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _serve_once(args, model_dir, cache_dir, tag, limit):
    """Start the server CLI, 4 requests, /metrics, SIGTERM. stdlib only."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    new_tokens = 8 if args.rehearse else 32
    cmd = [
        sys.executable, "-m", "areal_tpu.inference.server",
        "--model-path", model_dir, "--port", str(port),
        "--max-num-seqs", "16", "--max-model-len", "1024",
        f"--compilation-cache-dir={cache_dir}",
    ] + (["--dtype", "float32", "--page-size", "16", "--prefill-chunk", "16",
          "--max-model-len", "128"] if args.rehearse else [])
    deadline = time.monotonic() + limit
    base = f"http://127.0.0.1:{port}"
    with open(os.path.join(args.out, "server.log"), "a") as log:
        log.write(f"--- {tag} start: {' '.join(cmd)}\n")
        log.flush()
        proc = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT, env=_child_env(args),
            cwd=ROOT, start_new_session=True,
        )
        try:
            while True:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"server exited with {proc.returncode} before /health"
                    )
                try:
                    _http(base + "/health", timeout=5)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise RuntimeError("server never answered /health")
                    time.sleep(0.5)
            info = json.loads(_http(base + "/get_model_info"))
            answers = [
                json.loads(_http(base + "/generate", {
                    "input_ids": [3 + i, 5, 7, 11, 13, 17, 19, 23],
                    "sampling_params": {
                        "max_new_tokens": new_tokens, "greedy": True,
                    },
                }, timeout=max(1.0, deadline - time.monotonic())))
                for i in range(4)
            ]
            metrics = {}
            for line in _http(base + "/metrics").decode().splitlines():
                if line.startswith("areal_tpu_gen_compile"):
                    name, value = line.rsplit(" ", 1)
                    metrics[name[len("areal_tpu_gen_"):]] = float(value)
            os.killpg(proc.pid, signal.SIGTERM)
            proc.wait(timeout=30)
        finally:
            _kill_group(proc)
            proc.wait()
    return {
        "answered": sum(
            len(a.get("output_ids", [])) == new_tokens for a in answers
        ),
        "attn_impl": info["attn_impl"],
        "device": info["device"],
        "metrics": metrics,
    }


def phase_server(args, limit):
    """Runs in the orchestrator (stdlib only): the server child owns the
    chip. Two starts over one cache directory: after a restart, traffic
    pays no XLA compile for the programs the first start left on disk."""
    model_dir = os.path.join(args.out, "model")
    deadline = time.monotonic() + limit
    try:
        if not os.path.exists(os.path.join(model_dir, "model.safetensors")):
            # --phase server alone: fabricate in a process that exits first
            res = _run_child("fabricate", args, limit)
            if not res["ok"]:
                return {**res, "phase": "server"}
        # jax-free import: the orchestrator still starts no backend
        from areal_tpu.utils.compile_cache import resolve_cache_dir

        cache_dir = resolve_cache_dir()
        first = _serve_once(
            args, model_dir, cache_dir, "first", deadline - time.monotonic()
        )
        second = _serve_once(
            args, model_dir, cache_dir, "second", deadline - time.monotonic()
        )
    except Exception as e:  # noqa: BLE001 — reported as this phase's failure
        return _result("server", False, None,
                       error=f"{type(e).__name__}: {e}")
    hits = second["metrics"].get("compile_cache_hits_total", 0.0)
    checks = {
        "answered_4_of_4": first["answered"] == 4 and second["answered"] == 4,
        "same_device": first["device"] == second["device"],
        # the CPU backend of the installed jaxlib cannot read its own
        # persisted programs back, so only the chip is held to this
        "second_start_cache_hits": args.rehearse or hits > 0,
    }
    if not args.rehearse:
        checks["attn_is_kernel"] = second["attn_impl"] == "kernel"
    return _result(
        "server", all(checks.values()), second["device"], checks=checks,
        attn_impl=second["attn_impl"],
        compile={
            "first_start": first["metrics"],
            "second_start": second["metrics"],
            "cache_dir": cache_dir,
        },
    )


def _ppo_batch(vocab, n_seqs, lo, hi, seed):
    import numpy as np

    from areal_tpu.utils import data as data_utils

    rng = np.random.default_rng(seed)
    seqs = [
        rng.integers(0, vocab, size=int(rng.integers(lo, hi)))
        for _ in range(n_seqs)
    ]
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm = np.zeros_like(batch["input_ids"], np.int32)
    for i, s in enumerate(seqs):
        lm[i, len(s) // 4: len(s)] = 1
    batch["loss_mask"] = lm
    # behaviour logprobs where random weights put them (about -log vocab):
    # far from the policy's own, the decoupled loss's importance weight
    # would vanish and the gradient with it
    batch["logprobs"] = (
        rng.standard_normal(lm.shape).astype(np.float32) * 0.1
        - np.log(vocab).astype(np.float32)
    ) * lm
    batch["versions"] = np.where(lm > 0, 0, -1).astype(np.int32)
    # siblings of a group must disagree, or every advantage is zero
    batch["rewards"] = np.asarray(
        [float(i % 2) for i in range(n_seqs)], np.float32
    )
    return batch


def _state_bytes_per_device(engine):
    """Parameter + optimizer bytes each device really holds: summed over
    the shards, not read off the sharding's name."""
    import jax

    held = {}
    for leaf in jax.tree_util.tree_leaves((engine.params, engine.opt_state)):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def _one_update(args, cfg, fsdp, batch, attn):
    """Fresh engine → one ppo_update → (stats, bytes per device, peak).
    The bytes are the larger of before and after the update: a fresh
    optimizer state and an updated one are laid out by different programs."""
    import gc

    import jax

    from areal_tpu.api.cli_args import (
        MicroBatchSpec, OptimizerConfig, ParallelismConfig, PPOActorConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine

    acfg = PPOActorConfig(
        dtype="float32" if args.rehearse else "bfloat16",
        param_dtype="float32", gradient_checkpointing=True, attn_impl=attn,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        optimizer=OptimizerConfig(lr=1e-6, warmup_steps_proportion=0.0),
        parallel=ParallelismConfig(fsdp_parallel_size=fsdp),
        group_size=2, ppo_n_minibatches=1, group_reward_norm=True,
        recompute_logprob=True, use_decoupled_loss=True,
    )
    engine = SPMDTrainEngine(acfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, 16, len(batch["rewards"])),
        model_config=cfg, seed=args.seed,
    )
    fresh = _state_bytes_per_device(engine)
    actor = PPOActor(acfg, engine)
    with_adv = actor.compute_advantages(dict(batch))
    stats = dict(actor.ppo_update(with_adv)[0])
    lm = with_adv["loss_mask"].astype(bool)
    stats["adv_scale"] = float(abs(with_adv["advantages"][lm]).mean())
    held = {
        d: max(b, fresh.get(d, 0))
        for d, b in _state_bytes_per_device(engine).items()
    }
    peak = {
        d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    }
    # the next engine needs the room: device 0 cannot hold two of them
    engine.destroy()
    del actor, engine, with_adv
    gc.collect()
    stats["live_bytes_after_destroy"] = sum(
        a.nbytes for a in jax.live_arrays()
    )
    return stats, held, peak


def child_fsdp4(args, device, watch):
    import math

    from areal_tpu.utils import compile_cache

    attn = "auto" if args.rehearse else "flash"
    cfg = _model_config(args)
    lo, hi = (8, 16) if args.rehearse else (256, 512)
    batch = _ppo_batch(cfg.vocab_size, 8, lo, hi, args.seed)
    s1, held1, _ = _one_update(args, cfg, 1, batch, attn)
    s4, held4, _ = _one_update(args, cfg, 4, batch, attn)

    def rel(a, b, floor=1e-12):
        return abs(a - b) / max(abs(a), abs(b), floor)

    total1 = sum(held1.values())
    shares = {d: b / total1 for d, b in sorted(held4.items())}
    # (b) the model one chip cannot train: Qwen2-1.5B, f32 params + Adam
    big = _model_config(args, "qwen2_1p5b")
    try:
        sb, _, peak = _one_update(
            args, big, 4,
            _ppo_batch(big.vocab_size, 8, lo, hi, args.seed + 1), attn,
        )
    except Exception as e:  # noqa: BLE001 — (a)'s numbers still get printed
        sb = {"update_successful": 0.0, "loss": math.nan,
              "grad_norm": math.nan, "error": f"{type(e).__name__}: {e}"}
        peak = None
    checks = {
        "one_device_on_one_device": len(held1) == 1,
        # the loss is a mean of +-advantages that nearly cancel: held to the
        # advantages' scale, not to its own near-zero size
        "loss_agrees": (
            rel(s1["loss"], s4["loss"], s1["adv_scale"]) <= FSDP_REL_TOL
        ),
        "grad_norm_agrees": (
            rel(s1["grad_norm"], s4["grad_norm"]) <= FSDP_REL_TOL
        ),
        "four_devices_hold_shards": len(held4) == 4,
        "every_share_small": max(shares.values()) <= FSDP_SHARE_MAX,
        "big_step_finite": (
            sb["update_successful"] == 1.0
            and math.isfinite(sb["loss"]) and math.isfinite(sb["grad_norm"])
        ),
    }
    return _result(
        "fsdp4", all(checks.values()), device, checks=checks,
        attn_impl=attn,
        one_device={"loss": s1["loss"], "grad_norm": s1["grad_norm"],
                    "adv_scale": s1["adv_scale"], "state_bytes": total1},
        fsdp4={"loss": s4["loss"], "grad_norm": s4["grad_norm"],
               "state_share_per_device": shares},
        qwen2_1p5b={"vocab_size": big.vocab_size,
                    "num_layers": big.num_layers, "loss": sb["loss"],
                    "grad_norm": sb["grad_norm"], "error": sb.get("error"),
                    "peak_bytes_in_use_per_device": peak},
        live_bytes_after_destroy=[
            s["live_bytes_after_destroy"] for s in (s1, s4)
        ],
        compile=watch.report(compile_cache.enabled_dir()),
    )


def run_child(args):
    sys.path.insert(0, ROOT)
    # first: puts the scoped-VMEM flag into LIBTPU_INIT_ARGS before any
    # backend starts (the splash probe depends on it)
    import areal_tpu  # noqa: F401
    import jax

    from areal_tpu.utils import compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        res = _result(args.child, False, device,
                      error="no TPU: this phase has no CPU fallback")
    elif len(devs) < args.chips:
        res = _result(args.child, False, device,
                      error=f"needs {args.chips} devices")
    else:
        watch = _CompileWatch()
        compile_cache.enable_compilation_cache(
            compile_cache.resolve_cache_dir()
        )
        if args.child == "fabricate":
            _fabricate(args, _model_config(args))
            res = _result("fabricate", True, device)
        else:
            child = {"grpo": child_grpo, "parity": child_parity,
                     "fsdp4": child_fsdp4}[args.child]
            res = child(args, device, watch)
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse", action="store_true",
                   help="tiny model on the CPU (chosen here, never detected)")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded-trainer phase")
    p.add_argument("--phase", action="append",
                   help="run only this phase (repeatable)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                   help="checkpoint, data, logs (git ignores the default)")
    p.add_argument("--child", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    args.out = os.path.abspath(args.out)
    if args.child:
        return run_child(args)
    return orchestrate(args)


if __name__ == "__main__":
    sys.exit(main())
