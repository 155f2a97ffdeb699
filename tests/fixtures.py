"""Shared test fixtures: tiny tokenizer + tiny checkpoints + synthetic data.

Role of reference realhf/tests/fixtures.py:22-153 (random word-piece
tokenizer + synthetic jsonl datasets built on the fly).
"""

import contextlib
import json
import os
import queue
import subprocess
import sys
import threading
import time

import numpy as np

GENSERVER_WORKER = os.path.join(os.path.dirname(__file__), "genserver_worker.py")


def make_tiny_tokenizer(path: str, vocab_size: int = 128):
    """A word-level tokenizer over digits/operators, saved HF-style."""
    from tokenizers import Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    words = (
        ["<pad>", "<eos>", "<user>", "<assistant>"]
        + [str(i) for i in range(10)]
        + list("+-*/=()?.")
        + [
            "what", "is", "the", "answer", "sum", "of", "and", "compute",
            "####", "a", "b", "c", "x", "y",
        ]
    )
    vocab = {w: i for i, w in enumerate(words)}
    i = len(vocab)
    while len(vocab) < vocab_size:
        vocab[f"<extra{i}>"] = i
        i += 1
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<pad>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok,
        pad_token="<pad>",
        eos_token="<eos>",
    )
    fast.chat_template = (
        "{% for m in messages %}{{ '<' + m['role'] + '> ' + m['content'] + ' ' }}"
        "{% endfor %}{% if add_generation_prompt %}{{ '<assistant>' }}{% endif %}"
    )
    os.makedirs(path, exist_ok=True)
    fast.save_pretrained(path)
    return fast


def make_tiny_checkpoint(path: str, family: str = "qwen2", seed: int = 0):
    """Random tiny model in HF format (vocab matches the tiny tokenizer)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hf_io
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params

    cfg = tiny_config(family)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    hf_io.save_params(params, cfg, path)
    return cfg


def make_gsm8k_jsonl(path: str, n: int = 32, seed: int = 0):
    """Synthetic GSM8K-style rows: 'what is the sum of a and b ?' → a+b."""
    rng = np.random.default_rng(seed)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for _ in range(n):
            a, b = int(rng.integers(0, 5)), int(rng.integers(0, 5))
            digits_a = " ".join(str(a))
            digits_b = " ".join(str(b))
            q = f"what is the sum of {digits_a} and {digits_b} ?"
            ans_digits = " ".join(str(a + b))
            ansline = f"the answer is #### {ans_digits}"
            f.write(json.dumps({"question": q, "answer": ansline}) + "\n")
    return path


# ------------------------------------------------- children that serve --
def spawn_worker(cmd, env=None):
    """Start a child that serves until its stdin closes (``genserver_worker``,
    ``python -m areal_tpu.env.service``): (process, the queue a reader thread
    drains its output into for its whole life; an undrained pipe blocks the
    child's logging once the buffer fills)."""
    proc = subprocess.Popen(
        cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=env,
    )
    lines: "queue.Queue[str]" = queue.Queue()

    def drain():
        for line in proc.stdout:
            lines.put(line)

    threading.Thread(target=drain, daemon=True).start()
    return proc, lines


def spawn_env_worker(env_name, env_extra=None):
    """One real env-worker subprocess hosting ``areal_tpu.env.service``'s
    ``env_name``: (process, 'host:port')."""
    cmd = [
        sys.executable, "-m", "areal_tpu.env.service", "--env",
        f"areal_tpu.env.service:{env_name}", "--port", "0",
    ]
    proc, lines = spawn_worker(cmd, dict(os.environ, **(env_extra or {})))
    return proc, f"127.0.0.1:{wait_port(proc, lines, 60)}"


def wait_port(proc, lines, deadline_s=240.0):
    """The port the child reports (``PORT <n>``). A child that dies or stays
    silent past the deadline is an error; the child is killed on any way out
    but the port's, the per-test limit's included (it raises no
    ``Exception``), so that nothing is left holding a port."""
    deadline = time.monotonic() + deadline_s
    try:
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError("worker died before reporting a port")
            try:
                line = lines.get(timeout=1.0)
            except queue.Empty:
                continue
            if line.startswith("PORT "):
                return int(line.split()[1])
        raise RuntimeError("worker never reported a port")
    except BaseException:
        proc.kill()
        raise


def reap(proc, timeout=15.0):
    """End a child: close its stdin (its signal to leave), wait, and kill it
    if that did not end it, whatever interrupted the wait."""
    try:
        if proc.poll() is None:
            proc.stdin.close()
            proc.wait(timeout=timeout)
    except (OSError, ValueError, subprocess.TimeoutExpired):
        pass
    finally:
        if proc.poll() is None:
            proc.kill()


@contextlib.contextmanager
def children():
    """A list for a block's child processes: every one appended is reaped
    on the way out, whatever ended the block."""
    procs = []
    try:
        yield procs
    finally:
        for proc in procs:
            reap(proc)
