"""MoE decoder + expert parallelism (reference realhf/impl/model/modules/
moe/): routing correctness vs a per-token reference, dense-equivalence,
EP sharding parity, training, HF IO roundtrip, honest PP rejection.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import ParallelismConfig
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import apply, init_params
from areal_tpu.ops.moe import moe_ffn
from areal_tpu.parallel import mesh as mesh_lib


def _ref_moe(x, w_router, w_gate, w_up, w_down, k, norm):
    """Per-token numpy reference (no capacity limits)."""
    b, t, d = x.shape
    e = w_router.shape[-1]
    out = np.zeros((b, t, d), np.float32)
    for bi in range(b):
        for ti in range(t):
            h = x[bi, ti]
            logits = h @ w_router
            p = np.exp(logits - logits.max())
            p = p / p.sum()
            idx = np.argsort(-p)[:k]
            w = p[idx]
            if norm:
                w = w / w.sum()
            acc = np.zeros(d, np.float32)
            for j, ei in enumerate(idx):
                g = h @ w_gate[ei]
                u = h @ w_up[ei]
                silu = g / (1 + np.exp(-g)) * u
                acc += w[j] * (silu @ w_down[ei])
            out[bi, ti] = acc
    return out


def _weights(rng, d, f, e, scale=0.2):
    return (
        rng.standard_normal((d, e)).astype(np.float32) * 0.5,
        rng.standard_normal((e, d, f)).astype(np.float32) * scale,
        rng.standard_normal((e, d, f)).astype(np.float32) * scale,
        rng.standard_normal((e, f, d)).astype(np.float32) * scale,
    )


def test_moe_ffn_matches_per_token_reference():
    rng = np.random.default_rng(0)
    b, t, d, f, e, k = 2, 12, 8, 16, 4, 2
    x = rng.standard_normal((b, t, d)).astype(np.float32)
    wr, wg, wu, wd = _weights(rng, d, f, e)
    out, stats = jax.jit(
        lambda *a: moe_ffn(
            *a, num_experts_per_tok=k, norm_topk_prob=True,
        )
    )(jnp.asarray(x), jnp.asarray(wr), jnp.asarray(wg), jnp.asarray(wu),
      jnp.asarray(wd))
    ref = _ref_moe(x, wr, wg, wu, wd, k, norm=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)
    aux = float(stats.aux_loss)
    assert np.isfinite(aux) and aux >= 1.0  # ≥1 by Cauchy-Schwarz
    assert float(stats.assignments) == b * t * k


# how the router's load falls: nothing is ever dropped, so the dispatch
# equals the per-token reference whatever the imbalance. ``router`` turns the
# drawn router weights into the case's.
_LOADS = {
    "near_uniform": (2, lambda wr: wr * 0.01),
    "skewed": (2, lambda wr: wr + np.array([6.0, 2.0, 0, 0, 0, 0], np.float32)),
    # every token's first choice is expert 0: one group holds half the pairs
    "one_expert_in_every_pair": (
        2, lambda wr: wr * 0.1 + np.array([50.0, 0, 0, 0, 0, 0], np.float32)
    ),
    # k = 1 and one expert takes EVERY token: five groups are empty (the
    # capped dispatch this replaces dropped all but its capacity here)
    "all_tokens_on_one_expert": (
        1, lambda wr: wr * 0.1 + np.array([0, 0, 0, 50.0, 0, 0], np.float32)
    ),
}


@pytest.mark.parametrize("load", sorted(_LOADS))
@pytest.mark.parametrize("norm", [False, True], ids=["raw", "renormalised"])
def test_moe_dispatch_drops_nothing_under_any_load(load, norm):
    k, router = _LOADS[load]
    rng = np.random.default_rng(1)
    d, f, e = 8, 16, 6
    # positive features, so a constant added to a router column moves
    # every token the same way
    x = np.abs(rng.standard_normal((2, 20, d))).astype(np.float32)
    wr, wg, wu, wd = _weights(rng, d, f, e)
    wr = router(wr).astype(np.float32)
    out, stats = moe_ffn(
        jnp.asarray(x), jnp.asarray(wr), jnp.asarray(wg), jnp.asarray(wu),
        jnp.asarray(wd), num_experts_per_tok=k, norm_topk_prob=norm,
    )
    ref = _ref_moe(x, wr, wg, wu, wd, k, norm=norm)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)
    assert float(stats.assignments) == 40 * k
    busiest = float(stats.max_expert_assignments)
    if load == "all_tokens_on_one_expert":
        assert busiest == 40.0
    elif load == "one_expert_in_every_pair":
        assert busiest == 40.0  # once in each of the 40 pairs of two
    else:
        assert 40 * k / e <= busiest < 40 * k


def test_moe_dispatch_gradients_match_the_dense_form():
    """The sorted, grouped form is differentiated through a custom
    permutation rule and the grouped matmul's own: every gradient equals
    that of the same sum written densely (all experts, zero weights off
    the chosen ones)."""
    rng = np.random.default_rng(3)
    d, f, e, k = 8, 16, 6, 2
    x = jnp.asarray(rng.standard_normal((1, 24, d)), jnp.float32)
    ws = tuple(jnp.asarray(w) for w in _weights(rng, d, f, e))
    valid = jnp.asarray(np.arange(24) % 5 != 0)[None]

    def dense(x, wr, wg, wu, wd):
        p = jax.nn.softmax(x @ wr, axis=-1)
        chosen = jax.nn.one_hot(jax.lax.top_k(p, k)[1], e).sum(-2)
        y = jnp.einsum(
            "btef,efd->bted",
            jax.nn.silu(jnp.einsum("btd,edf->btef", x, wg))
            * jnp.einsum("btd,edf->btef", x, wu), wd,
        )
        return jnp.einsum("bte,bted->btd", p * chosen, y) * valid[..., None]

    target = jnp.asarray(rng.standard_normal((1, 24, d)), jnp.float32)
    got = jax.grad(
        lambda *a: (moe_ffn(
            *a, num_experts_per_tok=k, norm_topk_prob=False, valid=valid
        )[0] * target).sum(), argnums=(0, 1, 2, 3, 4),
    )(x, *ws)
    want = jax.grad(
        lambda *a: (dense(*a) * target).sum(), argnums=(0, 1, 2, 3, 4)
    )(x, *ws)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5
        )


@pytest.mark.parametrize("where", ["first", "last", "interleaved"])
def test_moe_padding_gets_zero_and_moves_no_real_token(where):
    """Invalid (padding / inactive-slot) tokens are no expert's load: they
    come out exactly zero, count in no statistic, and a real token's output
    is what it is alone — wherever the padding sits in the flat order, and
    though identical padding embeddings all route to the same experts."""
    rng = np.random.default_rng(2)
    d, f, e, k = 8, 16, 4, 2
    real = rng.standard_normal((1, 8, d)).astype(np.float32)
    n = 32
    at = {
        "first": np.arange(24, 32), "last": np.arange(8),
        "interleaved": np.arange(1, 32, 4),
    }[where]
    x = np.full((1, n, d), 0.7, np.float32)  # identical padding embeddings
    x[0, at] = real[0]
    valid = np.zeros((1, n), bool)
    valid[0, at] = True
    wr, wg, wu, wd = _weights(rng, d, f, e)
    args = [jnp.asarray(w) for w in (wr, wg, wu, wd)]
    kw = dict(num_experts_per_tok=k, norm_topk_prob=False)
    out, stats = moe_ffn(jnp.asarray(x), *args, valid=jnp.asarray(valid), **kw)
    alone, alone_stats = moe_ffn(jnp.asarray(real), *args, **kw)
    np.testing.assert_allclose(
        np.asarray(out)[0, at], np.asarray(alone)[0], rtol=1e-5, atol=1e-6
    )
    assert np.abs(np.asarray(out)[0, ~valid[0]]).max() == 0.0
    assert stats.stream_rows is None and stats.overflow_chunks is None
    for got, want in zip(stats[:3], alone_stats[:3]):  # every expert held
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


# A layer that HOLDS A SHARE (experts 4 and 5 of the 16 it routes over, two
# a token): 512 tokens are a stream of 1,024 pairs and a slab of
# 1024 x 2 / 16 x the slack of 2 = 256 rows. Each case: (tokens whose two
# pairs are both held here, tokens with one, which tokens are real, chunks,
# the rows the expert path must have run over, the chunks whose held pairs
# filled more than one slab).
_SHARE = dict(first=4, held=2, routed=16, k=2, tokens=512, buffer=256)
_HELD_LOADS = {
    "no_pair_held_here": (0, 0, None, 1, 256, 0),
    "under_the_buffer": (100, 3, None, 1, 256, 0),
    "exactly_the_buffer": (128, 0, None, 1, 256, 0),
    "one_past_the_buffer": (128, 1, None, 1, 512, 1),  # a second slab
    "every_pair_held_here": (512, 0, None, 1, 1024, 1),  # four
    # every token routes to the held experts, a fifth of them real
    "padding_first": (512, 0, np.arange(512) >= 410, 1, 256, 0),
    "padding_last": (512, 0, np.arange(512) < 102, 1, 256, 0),
    "padding_interleaved": (512, 0, np.arange(512) % 5 == 0, 1, 256, 0),
    # 1,024 tokens in two chunks: the first fills four slabs, the second one
    "two_chunks_one_overflows": (562, 7, None, 2, 1024 + 256, 1),
}


def _held_case(n_two, n_one, chunks):
    """Tokens whose routing is decided by two large features: the first
    ``n_two`` send both pairs to the held experts, the next ``n_one`` one,
    the others none."""
    s = _SHARE
    rng = np.random.default_rng(11)
    g, d, f = s["tokens"] * chunks, s["routed"], 16
    x = 0.05 * rng.standard_normal((1, g, d)).astype(np.float32)
    top = np.tile(np.array([9, 13]), (g, 1))  # held elsewhere
    top[:n_two] = (s["first"] + 1, s["first"])
    top[n_two:n_two + n_one, 0] = s["first"]
    x[0, np.arange(g), top[:, 0]] += 1.0
    x[0, np.arange(g), top[:, 1]] += 0.9
    wr = (10 * np.eye(d) + 0.1 * rng.standard_normal((d, d))).astype(np.float32)
    _, wg, wu, wd = _weights(rng, d, f, s["held"])
    return x, wr, wg, wu, wd


def _ref_share(x, wr, wg, wu, wd, k, first, valid):
    """``_ref_moe`` (renormalised) of the experts ``first`` and following."""
    out = np.zeros(x.shape, np.float32)
    for ti in np.flatnonzero(valid):
        h = x[0, ti]
        p = np.exp(h @ wr - (h @ wr).max())
        idx = np.argsort(-p)[:k]
        for ei in idx:
            if first <= ei < first + wg.shape[0]:
                gate = h @ wg[ei - first]
                out[0, ti] += p[ei] / p[idx].sum() * (
                    (gate / (1 + np.exp(-gate)) * (h @ wu[ei - first]))
                    @ wd[ei - first]
                )
    return out


def _banded_in_interpret_mode(monkeypatch, tiling):
    """The token side's kernel form off the chip (``moe.onto_impl`` is
    steered as ``tests/test_chip_compile_experts.py`` steers ``grouped_impl``), at
    tiles of a test's size."""
    from areal_tpu.ops import moe

    monkeypatch.setattr(moe, "onto_impl", lambda shape=None: "interpret")
    monkeypatch.setattr(moe, "_ONTO_ROWS", tiling[0])
    monkeypatch.setattr(moe, "_ONTO_TILE", tiling[1])


# (slab rows C, tokens G, width D, (rows a step, token tile, width tile),
# which rows are live: a share or a mask, the token of each live row: a
# function of the generator and the live rows' count, the weights: None = 1)
def _any_tokens(g):
    return lambda rng, n: rng.integers(0, g, n)


_ONTO_CASES = {
    # tokens 128..255 have no row: the second of four tiles comes out zero
    "a_tile_with_no_row": (
        512, 512, 48, (128, 128, 128), 0.8,
        lambda rng, n: rng.choice(
            np.r_[0:128, 256:512], n
        ), "random",
    ),
    # four rows a token, back to back and scattered over the slab
    "a_token_with_all_its_pairs": (
        512, 128, 48, (128, 128, 128), 1.0,
        lambda rng, n: rng.permutation(np.repeat(np.arange(128), 4)),
        "random",
    ),
    "every_live_row_in_one_tile": (
        512, 512, 48, (128, 128, 128), 0.5,
        lambda rng, n: rng.integers(256, 384, n), "random",
    ),
    "no_live_row_at_all": (
        256, 256, 48, (128, 128, 128), 0.0, _any_tokens(256), "random",
    ),
    # the live rows are every third: rows of no token lie between them
    # until the sort puts them behind
    "rows_of_no_token_interleaved": (
        384, 256, 48, (128, 128, 128), np.arange(384) % 3 == 0,
        _any_tokens(256), "random",
    ),
    "a_routing_weight_of_zero": (
        256, 256, 48, (128, 128, 128), 0.9, _any_tokens(256), "zeros",
    ),
    "weights_of_one": (  # the dispatch's transpose: w None
        256, 256, 48, (128, 128, 128), 0.9, _any_tokens(256), None,
    ),
    # the cells' slabs over a 4,096-token chunk at the row and token tiles
    # the chip runs, the width 2,304 cut to an eighth and NOT a whole number
    # of width tiles (2.25 of 128, as 2,304 is of 1,024; the chip's width
    # tile is the whole width)
    "kimi_slab_2048": (
        2048, 4096, 288, (256, 256, 128), 0.5, _any_tokens(4096), "random",
    ),
    "laguna_slab_4096": (
        4096, 4096, 288, (256, 256, 128), 0.5, _any_tokens(4096), "random",
    ),
    "mellum_slab_8192": (
        8192, 4096, 288, (256, 256, 128), 0.5, _any_tokens(4096), "random",
    ),
}


@pytest.mark.parametrize("case", sorted(_ONTO_CASES))
def test_a_slabs_rows_go_back_to_their_tokens_by_a_banded_product(
    case, monkeypatch
):
    """``ops/moe._onto_tokens`` alone: the grouped product over token
    tiles of the slab's rows in token order (the megablox kernel in Pallas
    interpret mode) against the product with the [G, C] matrix, in bf16 as
    the cells run it. The same products summed in float32, so they differ
    by the order of a token's sum at most: a rounding of the float32 sum,
    and one bf16 step where that moves the cast. What a row of no token
    holds (the grouped matmul leaves it unwritten) reaches nothing."""
    from areal_tpu.ops import moe

    c, g, d, tiling, live, tokens, weights = _ONTO_CASES[case]
    rng = np.random.default_rng(sorted(_ONTO_CASES).index(case))
    if not isinstance(live, np.ndarray):
        live = rng.random(c) < live
    tok = np.full(c, g, np.int32)
    tok[live] = tokens(rng, int(live.sum()))
    rows = rng.standard_normal((c, d)).astype(np.float32)
    rows[~live] = np.nan
    w = None
    if weights is not None:
        w = np.where(live, rng.random(c), 0).astype(np.float32)
        if weights == "zeros":
            w[np.flatnonzero(live)[::2]] = 0.0
        w = jnp.asarray(w)
    args = (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(tok), w, g)
    assert moe.onto_impl((c, g, d)) == "dense"  # the CPU's own choice
    dense = np.asarray(moe._onto_tokens(*args), np.float32)
    if w is None:  # through the rule, as ``_dispatch_held_bwd`` calls it
        _banded_in_interpret_mode(monkeypatch, tiling)
        banded = moe._onto_tokens(*args)
    else:  # the kernel form at the case's own tiles
        banded = moe.onto_tokens_banded(*args, tiling, interpret=True)
    banded = np.asarray(banded, np.float32)
    assert banded.shape == (g, d) and np.isfinite(banded).all()
    np.testing.assert_allclose(
        banded, dense, rtol=2.0 ** -7,
        atol=1e-6 * np.abs(rows[live]).max(initial=1.0),
    )
    # tokens with no live row come out as exact zeros in both
    empty = np.setdiff1d(np.arange(g), tok[live])
    assert not dense[empty].any() and not banded[empty].any()
    if live.any():
        assert np.abs(dense).max() > 0.1


# What a held-share case traces depends on the token side's form and on the
# number of chunks alone (the load is in the values: tokens and a mask of one
# shape), so the cases of one (form, chunks) run ONE set of compiled programs:
# the first case traces them under its patches, the others find them.
_HELD_PROGRAMS = {}


@pytest.mark.parametrize("onto", ["dense", "banded"])
@pytest.mark.parametrize("load", sorted(_HELD_LOADS))
def test_a_held_share_runs_over_a_bounded_buffer_and_drops_nothing(
    load, onto, monkeypatch
):
    """The expert path over slabs of the sorted stream against the whole
    stream's and the per-token reference: output, the gradients of the
    tokens, the router, the three expert stacks and the routing weights,
    and the two counters. A chunk whose held pairs pass one slab runs as
    many as they fill: nothing is dropped at any load. ``onto``: the slab's
    rows back onto their tokens by the dense product (what the CPU runs) or
    by the banded one (what a TPU runs; here in interpret mode)."""
    from areal_tpu.ops import moe

    n_two, n_one, valid, chunks, ran, overflowed = _HELD_LOADS[load]
    s = _SHARE
    if onto == "banded":
        _banded_in_interpret_mode(monkeypatch, (128, 128, 128))
    if chunks > 1:  # a stream of 1,024 rows is a chunk
        monkeypatch.setattr(moe, "_STREAM_ROWS", s["tokens"] * s["k"])
    programs = _HELD_PROGRAMS.setdefault((onto, chunks), {})

    def compiled(name, make):
        if name not in programs:
            programs[name] = make()
        return programs[name]

    np_args = _held_case(n_two, n_one, chunks)
    g = np_args[0].shape[1]
    valid = np.ones(g, bool) if valid is None else valid
    assert moe._held_rows(s["tokens"] * s["k"], s["held"], s["routed"]) == (
        s["buffer"]
    )

    def target(g):
        return jnp.cos(
            jnp.arange(g * s["routed"], dtype=jnp.float32)
        ).reshape(g, -1)

    def layer(valid, *a):
        out, stats = moe_ffn(
            *a, num_experts_per_tok=s["k"], norm_topk_prob=True,
            valid=valid[None], first_expert=s["first"],
        )
        return (out * target(out.shape[1])[None]).sum(), (out, stats)

    def grad_of_layer():
        return jax.jit(
            jax.grad(layer, argnums=(1, 2, 3, 4, 5), has_aux=True)
        )

    grads, (out, stats) = compiled("slabs", grad_of_layer)(valid, *np_args)
    # the whole stream: a buffer as long as the stream itself
    with monkeypatch.context() as whole_form:
        whole_form.setattr(moe, "_held_rows", lambda rows, e, n: rows)
        whole_grads, (whole, whole_stats) = compiled("whole", grad_of_layer)(
            valid, *np_args
        )
    held = 2 * min(n_two, g) + n_one if valid.all() else 2 * int(valid.sum())
    assert float(stats.assignments) == float(whole_stats.assignments) == held
    assert float(stats.stream_rows) == ran
    assert float(stats.overflow_chunks) == overflowed
    assert float(whole_stats.stream_rows) == g * s["k"]
    assert float(whole_stats.overflow_chunks) == 0
    np.testing.assert_allclose(
        np.asarray(out),
        _ref_share(*np_args, s["k"], s["first"], valid), rtol=2e-4, atol=2e-5,
    )
    for got, want in zip((out,) + grads, (whole,) + whole_grads):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )
    assert np.abs(np.asarray(out)[0, ~valid]).max(initial=0.0) == 0.0

    # the routing weights' gradient: one chunk's expert path on its own
    def routed(x, wr, valid):
        topk_p, topk_i, _ = moe.router_topk(x @ wr, s["k"], True)
        pair_expert = topk_i.reshape(-1) - s["first"]
        return topk_p, jnp.where(
            (pair_expert >= 0) & (pair_expert < s["held"])
            & jnp.repeat(valid, s["k"]),
            pair_expert, s["held"],
        ).astype(jnp.int32)

    xf = np_args[0][0, :s["tokens"]]
    topk_p, pair_expert = compiled("routed", lambda: jax.jit(routed))(
        xf, np_args[1], valid[:s["tokens"]]
    )

    def path(run):
        def f(xf, topk_p, pair_expert, *w):
            out = run(xf, pair_expert, topk_p, None, *w)
            out = out[0] if isinstance(out, tuple) else out
            return (out * target(s["tokens"])).sum()
        return lambda: jax.jit(jax.grad(f, argnums=(0, 1, 3, 4, 5)))

    for got, want in zip(
        compiled(
            "held_path", path(functools.partial(moe._run_held, c=s["buffer"]))
        )(xf, topk_p, pair_expert, *np_args[2:]),
        compiled("experts_path", path(moe._run_experts))(
            xf, topk_p, pair_expert, *np_args[2:]
        ),
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )


# The sparse layer's mesh form: a chip holds WHOLE experts, its share of them
# (``parallel/sharding.DEFAULT_RULES``: the expert dimension over ``expert``
# and ``fsdp``), the tokens are gathered to it and the chips' parts summed
# and scattered back. Each case: (family of ``tiny_config``, what it does to
# the layer's leaves, which tokens are padding).
_MESHES = {
    "fsdp4": dict(fsdp_parallel_size=4),
    "fsdp2_expert2": dict(fsdp_parallel_size=2, expert_parallel_size=2),
}


def _to_one_chip(lp):
    """Every token's two choices are experts 2 and 3, one chip's two: a
    constant feature (the tokens' first, set to 1) decides the router."""
    wr = 0.01 * np.asarray(lp["w_router"])
    wr[0, 2], wr[0, 3] = 20.0, 19.0
    return dict(lp, w_router=jnp.asarray(wr))


def _biased(lp):
    rng = np.random.default_rng(5)
    bias = 0.3 * rng.standard_normal(lp["router_bias"].shape)
    return dict(lp, router_bias=jnp.asarray(bias, jnp.float32))


_MESH_CASES = {
    "padding_in_a_row": ("olmoe", None, lambda i: i % 7 == 3),
    "every_pair_to_one_chip": ("olmoe", _to_one_chip, None),
    # Kimi: 8 held of 16 sigmoid-routed ones and a shared expert; Zaya: 4
    # held of 8, one a token, the scores an MLP's, handed in
    "shared_expert": ("kimi_linear", None, None),
    "sigmoid_router_bias": ("kimi_linear", _biased, lambda i: i < 9),
    "mlp_routers_scores": ("zaya", None, lambda i: i % 5 == 0),
}
_MESH_WANT = {}  # case -> what ONE device gives: computed once a case


def _mesh_case(case):
    from areal_tpu.models.transformer import param_logical_axes
    from areal_tpu.ops import moe
    from areal_tpu.parallel import sharding as sharding_lib

    family, change, padding = _MESH_CASES[case]
    cfg = tiny_config(family)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    layers, axes = params["layers"], param_logical_axes(cfg)["layers"]
    if cfg.layer_kinds:
        kind = next(k for k in layers if k.endswith("+moe"))
        layers, axes = layers[kind], axes[kind]
    layer = {k: v[0] for k, v in layers.items()}
    lp = {k: layer[k] for k in moe.sparse_leaves(cfg)}

    def specs(mesh):  # as ``engine/programs.py`` reads the mesh's table
        return {
            k: sharding_lib.logical_to_spec(axes[k][1:], mesh=mesh)
            for k in lp
        }

    rng = np.random.default_rng(4)
    h = rng.standard_normal((4, 128, cfg.hidden_size)).astype(np.float32)
    h[..., 0] = 1.0
    h = jnp.asarray(h)
    valid = np.ones((4, 128), bool)
    if padding is not None:
        valid[1] = ~padding(np.arange(128))
    scored = ()
    if cfg.router is not None:  # the scores come in beside the tokens
        scored = (moe.router_mlp(layer, h, cfg.rms_norm_eps)[0],)
    if change is not None:
        lp = change(lp)
    return cfg, lp, specs, h, jnp.asarray(valid), scored


def _layer_and_grads(run, n_scored):
    """jit of (the gradients of every leaf, of the tokens and of scores
    handed in; (the output, the statistics)) of a sum over the output plus
    the auxiliary loss."""

    def loss(lp, h, *scored):
        out, stats = run(lp, h, *scored)
        target = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
        return (out * target.reshape(out.shape)).sum() + stats.aux_loss, (
            out, stats
        )

    return jax.jit(
        jax.grad(loss, argnums=tuple(range(2 + n_scored)), has_aux=True)
    )


@pytest.mark.parametrize("case", sorted(_MESH_CASES))
@pytest.mark.parametrize("mesh", sorted(_MESHES))
def test_the_mesh_form_brings_the_tokens_to_the_experts(
    mesh, case, monkeypatch
):
    """The exchange against the one-device layer: the output, the gradient
    of every leaf, of the tokens and of scores handed in, the auxiliary loss
    and the load statistics; and the two counts of a share, summed over the
    chips. With every pair at ONE chip's experts and slabs of the even share
    (the slack of 2 taken away), that chip runs four slabs where the others
    run one: counted, and nothing dropped."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from areal_tpu.ops import moe

    if case == "every_pair_to_one_chip":
        monkeypatch.setattr(moe, "_HELD_SLACK", 1)
    cfg, lp, specs, h, valid, scored = _mesh_case(case)
    if case not in _MESH_WANT:
        _MESH_WANT[case] = _layer_and_grads(
            lambda lp, h, *s: moe.moe_ffn_from_params(
                cfg, lp, h, valid, None, *s
            ), len(scored),
        )(lp, h, *scored)
    want_grads, (want, want_stats) = _MESH_WANT[case]
    devices = mesh_lib.make_mesh(ParallelismConfig(**_MESHES[mesh]))
    specs = specs(devices)
    moe_fn = moe.make_sharded_moe_ffn(cfg, devices, specs)
    x_spec = P(("data", "fsdp"), "seq", None)

    def put(a, spec):
        return jax.device_put(a, NamedSharding(devices, spec))

    grads, (out, stats) = _layer_and_grads(
        lambda lp, h, *s: moe_fn(lp, h, valid, *s), len(scored)
    )(
        {k: put(v, specs[k]) for k, v in lp.items()}, put(h, x_spec),
        *(put(s, x_spec) for s in scored),
    )
    # a chip holds whole experts: a quarter of each stack, none of its width
    shard = grads[0]["w_gate"].addressable_shards[0].data.shape
    assert shard == (lp["w_gate"].shape[0] // 4,) + lp["w_gate"].shape[1:]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    if not cfg.shared_expert_size:  # which runs on every token
        assert np.abs(np.asarray(out)[~np.asarray(valid)]).max(initial=0) == 0
    for got, ref in zip(
        jax.tree.leaves(grads), jax.tree.leaves(want_grads), strict=True
    ):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(stats.aux_loss, want_stats.aux_loss, rtol=1e-6)
    assert float(stats.assignments) == float(want_stats.assignments)
    assert float(stats.max_expert_assignments) == float(
        want_stats.max_expert_assignments
    )
    # 512 tokens' pairs a chip; a quarter of the experts a chip (Zaya's and
    # Kimi's: of those the model holds, an eighth of those it routes over)
    rows = 512 * cfg.num_experts_per_tok
    slab = moe._held_rows(rows, cfg.num_held_experts // 4, cfg.num_experts)
    if case == "every_pair_to_one_chip":
        assert slab == rows // 4
        assert float(stats.assignments) == rows
        assert float(stats.stream_rows) == (4 + 3) * slab
        assert float(stats.overflow_chunks) == 1
    else:
        assert float(stats.stream_rows) == 4 * slab
        assert float(stats.overflow_chunks) == 0


def test_a_sum_across_chips_accumulates_in_float32_and_is_cast_once():
    """Both directions of the exchange: 1 + 3 x 2^-9 is 1.0078125 in bf16
    rounded once, and 1 where each chip's part is added in bf16."""
    from jax.sharding import PartitionSpec as P

    from areal_tpu.ops import moe

    devices = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=4))
    parts = np.full((4, 4, 2), 2.0 ** -9, np.float32)  # chip, row, width
    parts[0] = 1.0

    def on_chips(f):
        return jax.jit(jax.shard_map(
            f, mesh=devices, in_specs=P("fsdp"), out_specs=P("fsdp"),
            check_vma=False,
        ))

    chips_parts = jnp.asarray(parts.reshape(16, 2), jnp.bfloat16)
    summed = on_chips(lambda y: moe._to_their_owners(y, ("fsdp",), ()))(
        chips_parts
    )
    assert summed.dtype == jnp.bfloat16 and summed.shape == (4, 2)
    assert np.asarray(summed, np.float32).tolist() == [[1.0078125] * 2] * 4
    # the gather's transpose is the same sum
    back = on_chips(lambda g: jax.vjp(
        lambda x: moe._to_every_chip(x, ("fsdp",), ()),
        jnp.zeros((1, 2), jnp.bfloat16),
    )[1](g)[0])(chips_parts)
    assert back.dtype == jnp.bfloat16
    assert np.asarray(back, np.float32).tolist() == [[1.0078125] * 2] * 4


def test_a_mesh_that_does_not_divide_the_experts_is_refused_by_name():
    from areal_tpu.ops import moe

    cfg, _lp, specs, *_ = _mesh_case("padding_in_a_row")
    devices = mesh_lib.make_mesh(ParallelismConfig(fsdp_parallel_size=3))
    with pytest.raises(ValueError, match=r"\{'fsdp': 3\}.*8 experts"):
        moe.make_sharded_moe_ffn(cfg, devices, specs(devices))


def test_moe_model_forward_and_ep_parity():
    """Full qwen3_moe forward; EP=2-sharded params give identical logits
    to unsharded execution."""
    cfg = tiny_config("qwen3_moe")
    assert cfg.is_moe
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
    seg = jnp.ones((1, 16), jnp.int32)
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    logits = apply(params, cfg, tokens, seg, pos, remat=False)
    assert np.isfinite(np.asarray(logits)).all()

    # EP=2: shard expert weights over the expert axis
    from areal_tpu.models.transformer import param_logical_axes
    from areal_tpu.parallel import sharding as sharding_lib

    mesh = mesh_lib.make_mesh(
        ParallelismConfig(expert_parallel_size=2, fsdp_parallel_size=2)
    )
    shardings = sharding_lib.tree_shardings(
        mesh, param_logical_axes(cfg)
    )
    sharded = jax.device_put(params, shardings)
    logits_ep = jax.jit(
        lambda p: apply(p, cfg, tokens, seg, pos, remat=False)
    )(sharded)
    np.testing.assert_allclose(
        np.asarray(logits_ep), np.asarray(logits), rtol=2e-4, atol=2e-4
    )


def test_moe_training_step_with_aux_loss():
    from areal_tpu.api.cli_args import (
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.sft.lm_engine import sft_loss_fn, sft_loss_weight_fn
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine

    cfg = TrainEngineConfig(
        dtype="float32",
        param_dtype="float32",
        init_from_scratch=True,
        gradient_checkpointing=True,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        parallel=ParallelismConfig(
            fsdp_parallel_size=2, expert_parallel_size=2
        ),
    )
    engine = SPMDTrainEngine(cfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, 8, 4),
        model_config=tiny_config("qwen3_moe"),
        seed=0,
    )
    rng = np.random.default_rng(0)
    L = 24
    batch = {
        "input_ids": rng.integers(0, 128, size=(4, L)).astype(np.int32),
        "attention_mask": np.ones((4, L), np.bool_),
        "loss_mask": np.ones((4, L), np.int32),
    }
    losses = []
    for _ in range(3):  # step 0 is the lr-warmup step
        stats = engine.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
        assert stats["update_successful"] == 1.0
        assert np.isfinite(stats["router_aux_loss"])
        losses.append(stats["loss"])
    assert losses[-1] < losses[0]
    # the mesh splits the experts, a quarter a chip: the two counts of a
    # share are the chips' summed, and the dispatch spans name the form
    counters = engine.counters()
    assert set(counters) == {
        "real_tokens", "padded_tokens", "moe_assignments",
        "moe_max_expert_assignments", "moe_stream_rows",
        "moe_overflow_chunks",
    }
    assert engine._span_attrs["moe_mesh"] == "exchange"
    mc = engine.model_config
    assert mc.num_experts == 4 and mc.experts_held == 0  # one a chip
    pairs_a_token = mc.num_layers * mc.num_experts_per_tok
    assert counters["moe_assignments"] == 3 * 4 * L * pairs_a_token
    # each of the four chips runs one slab of the gathered rows' stream,
    # padding's rows among them: twice its even share of it, so half
    assert counters["moe_stream_rows"] == (
        4 * counters["padded_tokens"] * pairs_a_token // 2
    )
    assert stats["moe_overflow_chunks"] == 0


@pytest.mark.parametrize("family", ["qwen3_moe", "olmoe"])
def test_moe_hf_io_roundtrip(tmp_path, family):
    from areal_tpu.models import hf_io
    from areal_tpu.models.config import load_hf_config

    cfg = tiny_config(family)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    # q/k norms off their initial ones, so that a dropped or misshapen
    # norm (olmoe's is as wide as the projection, qwen3's as a head) shows
    rng = np.random.default_rng(3)
    for key in ("q_norm", "k_norm"):
        params["layers"][key] = jnp.asarray(
            1.0 + 0.1 * rng.standard_normal(params["layers"][key].shape),
            jnp.float32,
        )
    path = str(tmp_path / "moe_ckpt")
    hf_io.save_params(params, cfg, path)
    cfg2 = load_hf_config(path)
    assert cfg2 == cfg  # family, widths, router settings, the q/k norm's kind
    loaded = hf_io.load_params(path, cfg2, dtype=jnp.float32)
    assert set(loaded["layers"]) == set(params["layers"])
    for key in params["layers"]:
        np.testing.assert_allclose(
            np.asarray(loaded["layers"][key]),
            np.asarray(params["layers"][key]),
            rtol=1e-6,
        )
    if family == "olmoe":
        import json
        import os

        from safetensors import safe_open

        with open(os.path.join(path, "config.json")) as f:
            assert json.load(f)["architectures"] == ["OlmoeForCausalLM"]
        with safe_open(os.path.join(path, "model.safetensors"), "numpy") as f:
            names = set(f.keys())
        assert {
            "model.layers.0.mlp.gate.weight",
            "model.layers.1.mlp.experts.7.down_proj.weight",
            "model.layers.0.self_attn.q_norm.weight",
            "model.layers.0.self_attn.k_norm.weight",
            "lm_head.weight",
        } <= names
        assert f"model.layers.0.mlp.experts.{cfg.num_experts}.up_proj.weight" \
            not in names


def test_moe_generation_matches_full_forward():
    """MoE serving: the engine's prefill+decode path reproduces the
    training stack's forward token-for-token (greedy), incl. under tp=2
    expert sharding."""
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine

    cfg = tiny_config("qwen3_moe")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=9).tolist()

    # ground truth: full forward greedy continuation, every prefix in a row
    # of 16 tokens (one compiled program for the six lengths)
    forward = jax.jit(
        lambda t, seg, pos: apply(params, cfg, t, seg, pos, remat=False)
    )

    def full_next(seq):
        n = len(seq)
        t = np.zeros((1, 16), np.int32)
        t[0, :n] = seq
        seg = (np.arange(16) < n).astype(np.int32)[None]
        pos = (np.arange(16) * seg[0]).astype(np.int32)[None]
        return int(np.argmax(np.asarray(forward(t, seg, pos))[0, n - 1]))

    seq = list(prompt)
    for _ in range(6):
        seq.append(full_next(seq))
    expected = seq[len(prompt):]

    for tp in (1, 2):
        eng = GenerationEngine(
            JaxGenConfig(
                dtype="float32", max_num_seqs=4, max_model_len=64,
                prefill_chunk=16, tensor_parallel_size=tp,
            ),
            model_config=cfg, params=params,
        ).start()
        try:
            out = eng.generate(
                {
                    "input_ids": prompt,
                    "sampling_params": {"max_new_tokens": 6, "greedy": True},
                }
            )
            assert out["output_ids"] == expected, (tp, out["output_ids"])
        finally:
            eng.stop()


def test_pipeline_parallel_rejected():
    from areal_tpu.api.alloc_mode import (
        AllocationValidationError,
        ParallelStrategy,
    )

    ps = ParallelStrategy.from_str("d2t2p2")
    with pytest.raises(AllocationValidationError, match="pipeline"):
        ps.to_tpu_parallelism()
    # e is carved out of d·c (DSL: experts shard within the data/context
    # degrees)
    pc = ParallelStrategy.from_str("d4e2").to_tpu_parallelism()
    assert pc.expert_parallel_size == 2
    assert pc.fsdp_parallel_size == 2
    assert pc.world_size == ParallelStrategy.from_str("d4e2").world_size
    pc = ParallelStrategy.from_str("d2c2e4").to_tpu_parallelism()
    assert pc.expert_parallel_size == 4
    assert pc.fsdp_parallel_size == 1 and pc.seq_parallel_size == 1
    with pytest.raises(AllocationValidationError, match="divide"):
        ParallelStrategy.from_str("d3e2").to_tpu_parallelism()


class TestQwen2Moe:
    """qwen2_moe: shared expert + sigmoid gate on top of routed experts
    (HF Qwen2MoeSparseMoeBlock semantics)."""

    def test_from_hf_config_and_rejection(self):
        from areal_tpu.models.config import from_hf_config

        d = {
            "model_type": "qwen2_moe", "vocab_size": 128,
            "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_experts": 4,
            "num_experts_per_tok": 2, "moe_intermediate_size": 32,
            "shared_expert_intermediate_size": 48,
        }
        cfg = from_hf_config(d)
        assert cfg.is_moe and cfg.shared_expert_size == 48
        assert cfg.norm_topk_prob is False  # qwen2_moe default
        assert cfg.attention_bias
        import pytest as _pytest

        with _pytest.raises(ValueError, match="mlp_only_layers"):
            from_hf_config({**d, "mlp_only_layers": [0]})

    def test_shared_expert_contributes_and_trains(self):
        import jax
        import jax.numpy as jnp

        from areal_tpu.api.cli_args import (
            MicroBatchSpec,
            OptimizerConfig,
            ParallelismConfig,
            TrainEngineConfig,
        )
        from areal_tpu.api.io_struct import FinetuneSpec
        from areal_tpu.engine.sft.lm_engine import (
            sft_loss_fn,
            sft_loss_weight_fn,
        )
        from areal_tpu.engine.spmd_engine import SPMDTrainEngine
        from areal_tpu.models.config import tiny_config
        from areal_tpu.models.transformer import apply, init_params

        cfg = tiny_config("qwen2_moe")
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        assert "w_shared_gate" in params["layers"]
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 128, size=(1, 12)), jnp.int32)
        seg = jnp.ones((1, 12), jnp.int32)
        pos = jnp.arange(12)[None]
        base = apply(params, cfg, toks, seg, pos, remat=False)
        # zeroing the shared expert changes the logits: it really runs
        p2 = jax.tree_util.tree_map(lambda x: x, params)
        p2["layers"] = dict(p2["layers"])
        p2["layers"]["w_shared_down"] = jnp.zeros_like(
            p2["layers"]["w_shared_down"]
        )
        off = apply(p2, cfg, toks, seg, pos, remat=False)
        assert float(jnp.abs(base - off).max()) > 1e-4

        tcfg = TrainEngineConfig(
            dtype="float32", param_dtype="float32",
            gradient_checkpointing=False,
            mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
            optimizer=OptimizerConfig(lr=1e-2, warmup_steps_proportion=0.0),
            parallel=ParallelismConfig(),
        )
        eng = SPMDTrainEngine(tcfg)
        eng.initialize(FinetuneSpec(1, 8, 2), model_config=cfg, seed=0)
        before = jax.device_get(eng.params["layers"]["w_shared_gate"])
        batch = {
            "input_ids": rng.integers(0, 128, size=(2, 16)).astype(np.int64),
            "attention_mask": np.ones((2, 16), np.bool_),
            "loss_mask": np.ones((2, 16), np.int64),
        }
        stats = eng.train_batch(batch, sft_loss_fn, sft_loss_weight_fn)
        assert stats["update_successful"] == 1.0
        after = jax.device_get(eng.params["layers"]["w_shared_gate"])
        assert np.abs(np.asarray(after) - np.asarray(before)).max() > 0

    def test_hf_roundtrip(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from areal_tpu.models import hf_io
        from areal_tpu.models.config import load_hf_config, tiny_config
        from areal_tpu.models.transformer import apply, init_params

        cfg = tiny_config("qwen2_moe")
        params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
        path = str(tmp_path / "q2moe")
        hf_io.save_params(params, cfg, path)
        cfg2 = load_hf_config(path)
        assert cfg2.shared_expert_size == cfg.shared_expert_size
        loaded = hf_io.load_params(path, cfg2, dtype=jnp.float32)
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, 128, size=(1, 10)), jnp.int32)
        seg = jnp.ones((1, 10), jnp.int32)
        pos = jnp.arange(10)[None]
        a = apply(params, cfg, toks, seg, pos, remat=False)
        b = apply(loaded, cfg2, toks, seg, pos, remat=False)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )
