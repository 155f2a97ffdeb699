"""Paged KV decode correctness: incremental == full forward.

The inference engine's whole correctness story rests on prefill+decode
over the page pool reproducing the training stack's forward pass
token-for-token (reference analog: SGLang serving correctness the
reference assumes; areal/engine/sglang_remote.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.inference import model_runner
from areal_tpu.inference.cache import CacheConfig, init_kv_pool
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import apply, init_params
from areal_tpu.ops.paged_attention import unpacked_view

BS = 16  # page size (tokens)
NSLOTS = 4
PAGES_PER_SLOT = 4  # 64 tokens per slot
NPAGES = NSLOTS * PAGES_PER_SLOT + 1  # page 0 reserved (merge drop target)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ccfg = CacheConfig(num_pages=NPAGES, page_size=BS, max_model_len=64)
    return cfg, params, ccfg


def _tables():
    """Disjoint page tables: slot s owns pages [1+s*4, 1+s*4+4) (page 0 is
    the reserved trash target for dropped merge rows)."""
    return (
        1 + np.arange(NSLOTS)[:, None] * PAGES_PER_SLOT
        + np.arange(PAGES_PER_SLOT)[None]
    ).astype(np.int32)


class Harness:
    """Threads the per-slot last_rows state between dispatches (the engine
    does the same)."""

    def __init__(self, cfg):
        from areal_tpu.inference.model_runner import init_last_rows
        from areal_tpu.ops.paged_attention import pack_factor

        fd = pack_factor(cfg.head_dim) * cfg.head_dim
        self.last = init_last_rows(
            cfg.num_layers, NSLOTS, cfg.num_kv_heads, fd, jnp.float32
        )

    def prefill_one(self, params, cfg, cache, prompt, slot, offset=0):
        suffix = prompt[offset:]
        tp = max(16, -(-len(suffix) // 16) * 16)
        padded = np.zeros((1, tp), np.int32)
        padded[0, : len(suffix)] = suffix
        tables = _tables()[slot : slot + 1]
        cache, logits, new_last = model_runner.prefill_batch(
            params, cfg, cache, jnp.asarray(padded),
            jnp.asarray([offset], jnp.int32),
            jnp.asarray([len(suffix)], jnp.int32),
            jnp.asarray(tables),
            prefix_bound=(BS * PAGES_PER_SLOT if offset else 0),
            last_rows=self.last,
            slot_ids=jnp.asarray([slot], jnp.int32),
        )
        for kk in ("k", "v"):
            self.last[kk] = self.last[kk].at[:, slot].set(new_last[kk][:, 0])
        return cache, logits[0]

    def decode_step(self, params, cfg, cache, tables, pos0, tokens, active):
        cache, logits, self.last = model_runner.decode_step(
            params, cfg, cache, tables, pos0, tokens, active,
            last_rows=self.last,
        )
        return cache, logits

    def decode_multi(self, params, cfg, cache, *args, **kw):
        out = model_runner.decode_multi(
            params, cfg, cache, *args, last_rows=self.last, **kw
        )
        # r14: decode_multi always returns (..., new_last, next_tokens)
        self.last = out[8]
        return out[:8]


def _full_forward_argmax(params, cfg, tokens):
    t = jnp.asarray(tokens, jnp.int32)[None]
    seg = jnp.ones_like(t)
    pos = jnp.arange(t.shape[1], dtype=jnp.int32)[None]
    logits = apply(params, cfg, t, seg, pos, remat=False)
    return int(jnp.argmax(logits[0, -1])), np.asarray(logits[0, -1])


def _slot_kv(cache, cfg, slot, n):
    """First n cached (k, v) rows of a slot via its page table."""
    view = unpacked_view(cache["k"], cfg.head_dim)  # [L,Hkv,NP,BS,D]
    pages = _tables()[slot]
    k = np.asarray(view[:, :, pages]).reshape(
        view.shape[0], view.shape[1], -1, cfg.head_dim
    )[:, :, :n]
    vview = unpacked_view(cache["v"], cfg.head_dim)
    v = np.asarray(vview[:, :, pages]).reshape(
        view.shape[0], view.shape[1], -1, cfg.head_dim
    )[:, :, :n]
    return k, v


def test_greedy_decode_matches_full_forward(setup):
    cfg, params, ccfg = setup
    cache = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    h = Harness(cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=7).tolist()

    cache, logits = h.prefill_one(params, cfg, cache, prompt, slot=0)
    ref_tok, ref_logits = _full_forward_argmax(params, cfg, prompt)
    np.testing.assert_allclose(
        np.asarray(logits), ref_logits, rtol=1e-4, atol=1e-4
    )
    seq = list(prompt)
    tok = int(jnp.argmax(logits))
    assert tok == ref_tok

    pos0 = np.zeros(NSLOTS, np.int32)
    pos0[0] = len(prompt)
    # 6 greedy decode steps, checking against full recompute each time
    for _ in range(6):
        seq.append(tok)
        tokens = jnp.zeros((NSLOTS,), jnp.int32).at[0].set(tok)
        active = jnp.zeros((NSLOTS,), bool).at[0].set(True)
        cache, logits = h.decode_step(
            params, cfg, cache, jnp.asarray(_tables()),
            # a numpy copy: the step is dispatched asynchronously and pos0
            # is advanced right after; on the CPU backend jnp.array reads
            # the buffer late too (jax 0.9: wrong in ~47% of 3000 tries)
            jnp.asarray(pos0.copy()), tokens, active,
        )
        pos0[0] += 1
        ref_tok, ref_logits = _full_forward_argmax(params, cfg, seq)
        np.testing.assert_allclose(
            np.asarray(logits[0]), ref_logits, rtol=1e-4, atol=1e-4
        )
        tok = int(jnp.argmax(logits[0]))
        assert tok == ref_tok


def test_two_slots_decode_independently(setup):
    cfg, params, ccfg = setup
    cache = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    h = Harness(cfg)
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, cfg.vocab_size, size=5).tolist()
    p1 = rng.integers(0, cfg.vocab_size, size=9).tolist()
    cache, l0 = h.prefill_one(params, cfg, cache, p0, slot=0)
    cache, l1 = h.prefill_one(params, cfg, cache, p1, slot=1)
    t0, t1 = int(jnp.argmax(l0)), int(jnp.argmax(l1))
    tokens = jnp.zeros((NSLOTS,), jnp.int32).at[0].set(t0).at[1].set(t1)
    active = jnp.zeros((NSLOTS,), bool).at[0].set(True).at[1].set(True)
    pos0 = np.zeros(NSLOTS, np.int32)
    pos0[0], pos0[1] = len(p0), len(p1)
    cache, logits = h.decode_step(
        params, cfg, cache, jnp.asarray(_tables()), jnp.asarray(pos0),
        tokens, active,
    )
    ref0, _ = _full_forward_argmax(params, cfg, p0 + [t0])
    ref1, _ = _full_forward_argmax(params, cfg, p1 + [t1])
    assert int(jnp.argmax(logits[0])) == ref0
    assert int(jnp.argmax(logits[1])) == ref1


def test_prefill_offset_matches_full(setup):
    """Page-aligned suffix prefill (prefix reuse) == full prefill."""
    cfg, params, ccfg = setup
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=2 * BS + 5).tolist()
    cache_f = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    hf = Harness(cfg)
    cache_f, logits_f = hf.prefill_one(params, cfg, cache_f, prompt, slot=0)

    cache_r = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    hr = Harness(cfg)
    # cache the first 2 pages via a full prefill, then re-prefill only the
    # suffix with offset 2*BS
    cache_r, _ = hr.prefill_one(params, cfg, cache_r, prompt, slot=0)
    cache_r, logits_r = hr.prefill_one(
        params, cfg, cache_r, prompt, slot=0, offset=2 * BS
    )
    np.testing.assert_allclose(
        np.asarray(logits_r), np.asarray(logits_f), rtol=1e-4, atol=1e-4
    )
    k_f, v_f = _slot_kv(cache_f, cfg, 0, len(prompt))
    k_r, v_r = _slot_kv(cache_r, cfg, 0, len(prompt))
    np.testing.assert_allclose(k_r, k_f, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v_r, v_f, rtol=1e-5, atol=1e-5)


def test_decode_multi_matches_stepwise(setup):
    """Fused multi-step decode (chunk-buffer attention) == repeated
    decode_step + greedy sampling, including cache state and early stop."""
    cfg, params, ccfg = setup
    s = NSLOTS
    rng = np.random.default_rng(7)
    p0 = rng.integers(0, cfg.vocab_size, size=6).tolist()
    p1 = rng.integers(0, cfg.vocab_size, size=9).tolist()

    def prefill_two(cache, h):
        cache, l0 = h.prefill_one(params, cfg, cache, p0, slot=0)
        cache, l1 = h.prefill_one(params, cfg, cache, p1, slot=1)
        return cache, l0, l1

    cache_a = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    ha = Harness(cfg)
    cache_a, l0, l1 = prefill_two(cache_a, ha)
    cache_b = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    hb = Harness(cfg)
    cache_b, _, _ = prefill_two(cache_b, hb)

    t0, t1 = int(jnp.argmax(l0)), int(jnp.argmax(l1))
    tokens = jnp.zeros((s,), jnp.int32).at[0].set(t0).at[1].set(t1)
    active = jnp.zeros((s,), bool).at[0].set(True).at[1].set(True)
    pos0 = np.zeros(s, np.int32)
    pos0[0], pos0[1] = len(p0), len(p1)
    steps = 5
    greedy = jnp.ones(s, bool)
    ones = jnp.ones(s)
    zk = jnp.zeros(s, jnp.int32)
    tb = jnp.asarray(_tables())

    # A: fused decode_multi
    cache_a, toks_a, logps_a, emitted_a, active_a, _, _, lens_a = (
        ha.decode_multi(
            params, cfg, cache_a, tb, jnp.asarray(pos0), tokens, active,
            jnp.full((s,), 100, jnp.int32), jnp.zeros(s, jnp.int32),
            jnp.full((s, 4), -1, jnp.int32), jax.random.PRNGKey(0),
            ones, ones, zk, greedy, steps=steps,
        )
    )
    # B: stepwise decode_step + argmax
    cur = tokens
    pos_b = pos0.copy()
    toks_b = []
    for _ in range(steps):
        cache_b, logits = hb.decode_step(
            params, cfg, cache_b, tb, jnp.asarray(pos_b.copy()), cur, active
        )
        pos_b[0] += 1
        pos_b[1] += 1
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks_b.append(np.asarray(nxt))
        cur = nxt
    toks_b = np.stack(toks_b)
    np.testing.assert_array_equal(
        np.asarray(toks_a)[:, :2], toks_b[:, :2]
    )
    assert bool(np.all(np.asarray(emitted_a)[:, :2]))
    assert int(lens_a[0]) == len(p0) + steps and int(lens_a[1]) == len(p1) + steps
    # cache state converged identically (active slots' pages)
    for slot, plen in ((0, len(p0)), (1, len(p1))):
        ka, va = _slot_kv(cache_a, cfg, slot, plen + steps)
        kb, vb = _slot_kv(cache_b, cfg, slot, plen + steps)
        np.testing.assert_allclose(ka, kb, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-5)

    # early stop inside the chunk: use the 3rd emitted token as a stop id
    stop_id = int(toks_b[2, 0])
    cache_c = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    hc = Harness(cfg)
    cache_c, _, _ = prefill_two(cache_c, hc)
    stops = jnp.full((s, 4), -1, jnp.int32).at[0, 0].set(stop_id)
    cache_c, toks_c, _, emitted_c, active_c, _, _, _ = (
        hc.decode_multi(
            params, cfg, cache_c, tb, jnp.asarray(pos0), tokens, active,
            jnp.full((s,), 100, jnp.int32), jnp.zeros(s, jnp.int32),
            stops, jax.random.PRNGKey(0),
            ones, ones, zk, greedy, steps=steps,
        )
    )
    em = np.asarray(emitted_c)[:, 0]
    # slot 0 emitted exactly 3 tokens (stop token is the 3rd)
    assert em.sum() == 3 and not bool(active_c[0])
    # slot 1 unaffected
    np.testing.assert_array_equal(np.asarray(toks_c)[:, 1], toks_b[:, 1])


def test_copy_pages(setup):
    """Page copy duplicates KV content (sibling partial-tail fan-out)."""
    cfg, params, ccfg = setup
    cache = init_kv_pool(cfg, ccfg, dtype=jnp.float32)
    h = Harness(cfg)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=BS + 3).tolist()
    cache, _ = h.prefill_one(params, cfg, cache, prompt, slot=0)
    # copy slot 0's partial tail page (page index 1) to slot 1's first page
    src = jnp.asarray([_tables()[0, 1]], jnp.int32)
    dst = jnp.asarray([_tables()[1, 0]], jnp.int32)
    cache = model_runner.copy_pages(cache, src, dst)
    view = unpacked_view(cache["k"], cfg.head_dim)
    np.testing.assert_array_equal(
        np.asarray(view[:, :, int(src[0])]), np.asarray(view[:, :, int(dst[0])])
    )


def test_sampling_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray(
        np.log(np.asarray([[0.5, 0.3, 0.15, 0.05]], np.float32))
    )
    s = logits.shape[0]
    # greedy
    tok, logp = model_runner.sample_tokens(
        logits, key, jnp.ones(s), jnp.ones(s), jnp.zeros(s, jnp.int32),
        jnp.ones(s, bool),
    )
    assert int(tok[0]) == 0
    np.testing.assert_allclose(float(logp[0]), np.log(0.5), rtol=1e-5)
    # top_k=1 → argmax even without greedy
    tok2, _ = model_runner.sample_tokens(
        logits, key, jnp.ones(s), jnp.ones(s),
        jnp.ones(s, jnp.int32), jnp.zeros(s, bool),
    )
    assert int(tok2[0]) == 0
    # top_p=0.6 excludes tokens 2,3; sample many times and check support
    toks = []
    for i in range(50):
        t, _ = model_runner.sample_tokens(
            logits, jax.random.PRNGKey(i), jnp.ones(s),
            jnp.full((s,), 0.6), jnp.zeros(s, jnp.int32), jnp.zeros(s, bool),
        )
        toks.append(int(t[0]))
    assert set(toks) <= {0, 1}
    assert len(set(toks)) == 2  # temperature 1: both appear in 50 draws


def test_topk_bound_sampling_matches_exact():
    """Bounded top_k sampling draws from the SAME truncated distribution as
    the exact full-sort path (same support, matching frequencies) whenever
    the truncation set fits inside the bound. The two paths use different
    sample shapes, so tokens differ per-key — the distribution is the
    contract."""
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32)) * 3.0
    s = logits.shape[0]
    temp = jnp.asarray([1.0, 0.7, 1.3, 1.0])
    top_p = jnp.asarray([0.9, 1.0, 0.8, 0.95])
    top_k = jnp.asarray([5, 20, 10, 50], jnp.int32)
    greedy = jnp.zeros(s, bool)
    n_draws = 400
    exact = np.zeros((n_draws, s), np.int64)
    fast = np.zeros((n_draws, s), np.int64)
    for seed in range(n_draws):
        key = jax.random.PRNGKey(seed)
        t_exact, lp_exact = model_runner.sample_tokens(
            logits, key, temp, top_p, top_k, greedy, topk_bound=0
        )
        t_fast, lp_fast = model_runner.sample_tokens(
            logits, key, temp, top_p, top_k, greedy, topk_bound=64
        )
        exact[seed] = np.asarray(t_exact)
        fast[seed] = np.asarray(t_fast)
        # behavior logprob is truncation-independent: same token → same logp
        scaled = np.asarray(logits) / np.asarray(temp)[:, None]
        ref_lp = scaled - np.log(np.exp(scaled).sum(-1, keepdims=True))
        for i in range(s):
            np.testing.assert_allclose(
                float(lp_fast[i]), ref_lp[i, int(t_fast[i])], rtol=1e-4
            )
    for i in range(s):
        sup_exact = set(np.unique(exact[:, i]))
        sup_fast = set(np.unique(fast[:, i]))
        # identical support modulo sampling noise on ultra-rare tail members
        assert sup_fast == sup_exact or (
            len(sup_fast ^ sup_exact) <= max(2, len(sup_exact) // 5)
        )
        # the modal token matches and its frequency is close
        vals, counts = np.unique(exact[:, i], return_counts=True)
        mode = vals[np.argmax(counts)]
        f_exact = (exact[:, i] == mode).mean()
        f_fast = (fast[:, i] == mode).mean()
        assert abs(f_exact - f_fast) < 0.12


def test_free_mode_sampling_logprobs():
    """topk_bound=-1 (no truncation): logprob still the temperature-scaled
    behavior logprob."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
    temp = jnp.asarray([0.8, 1.0])
    ones = jnp.ones(2)
    toks, lps = model_runner.sample_tokens(
        logits, jax.random.PRNGKey(0), temp, ones,
        jnp.zeros(2, jnp.int32), jnp.zeros(2, bool), topk_bound=-1,
    )
    ref = jax.nn.log_softmax(logits / temp[:, None], axis=-1)
    for i in range(2):
        np.testing.assert_allclose(
            float(lps[i]), float(ref[i, int(toks[i])]), rtol=1e-5
        )


def test_mixed_truncation_keeps_untruncated_exact():
    """When one slot requests top_k and another requests none, the
    untruncated slot must sample from the FULL vocabulary even on the
    fast topk_bound path (round-2 advisor finding)."""
    v = 64
    rng = np.random.default_rng(11)
    base = jnp.asarray(rng.standard_normal((2, v)), jnp.float32)
    seen = set()
    for i in range(200):
        t, _ = model_runner.sample_tokens(
            base, jax.random.PRNGKey(i),
            jnp.full((2,), 2.0),  # flatten the distribution
            jnp.ones(2), jnp.asarray([4, 0], jnp.int32),
            jnp.zeros(2, bool), topk_bound=4,
        )
        seen.add(int(t[1]))
    # the untruncated slot must escape the top-4 candidate set
    top4 = set(np.asarray(jax.lax.top_k(base[1], 4)[1]).tolist())
    assert seen - top4, "untruncated slot never sampled outside top-4"
