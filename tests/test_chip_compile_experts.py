"""A layer that holds a share of its experts, at three cells' widths, compiled
for a described v5e: nothing runs (``chip_compile_fixture`` says what these
check)."""

import re

import pytest

from chip_compile_fixture import _sds, one_chip  # noqa: F401


# (model width, expert width, experts routed over, experts held, a token):
# one 4,096-token chunk of three of the cells whose layer holds a share
_HELD_CASES = [
    pytest.param(2048, 768, 128, 16, 8, id="keye-16-of-128"),
    pytest.param(2304, 1024, 256, 8, 8, id="kimi-8-of-256"),
    pytest.param(2304, 896, 64, 8, 8, id="mellum-8-of-64"),
]


@pytest.mark.parametrize("d,f,n_routed,e,k", _HELD_CASES)
def test_held_share_layer_fwd_bwd_compiles_for_v5e(
    one_chip, monkeypatch, d, f, n_routed, e, k
):
    """``ops/moe.moe_ffn`` of a layer that HOLDS A SHARE, forward and
    backward at one token chunk of the long-row, the Kimi and the Mellum
    cell: the expert path runs over slabs of twice the even share (8,192,
    2,048 and 8,192 rows of the 32,768-row stream): a loop over the slabs
    forward, the first slab in line and a loop over the others backward. No
    array of the whole stream's rows by the model's width is left, the
    expert kernels keep the names ``moe_experts_roofline.train`` matches,
    and their number is held (each is lowered in Python at every
    occurrence), not only bounded below. A slab's rows go back onto their
    tokens by the banded product (``moe.onto_tokens_banded``: the same
    megablox kernel under a name of its own, which that metric does not
    match): once in the forward loop's body (the combine) and once in the
    backward's first slab and in its loop's body (the dispatch's transpose;
    the combine the backward traces again has no reader and is gone), and
    no [tokens, slab] matrix is left."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import moe
    from benchmark import program_trace

    monkeypatch.setattr(moe, "grouped_impl", lambda shape=None: "megablox")
    monkeypatch.setattr(moe, "onto_impl", lambda shape=None: "banded")
    t = 4096
    c = moe._held_rows(t * k, e, n_routed)
    assert c == 2 * t * k * e // n_routed

    def loss(x, wr, wg, wu, wd, valid):
        out, stats = moe.moe_ffn(
            x, wr, wg, wu, wd, num_experts_per_tok=k, norm_topk_prob=True,
            valid=valid,
        )
        # not linear in ``out``: the backward reads the forward's result,
        # so the forward loop stays in the program
        return (out.astype(jnp.float32) ** 2).sum(), stats

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        .lower(
            _sds((1, t, d), jnp.bfloat16, one_chip),
            _sds((d, n_routed), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, d, f), jnp.bfloat16, one_chip),
            _sds((e, f, d), jnp.bfloat16, one_chip),
            _sds((1, t), jnp.bool_, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    # the forward loop's body (3 gmm, the combine's banded product), the
    # backward's first slab and its loop's body (each 3 gmm again, 3 gmm
    # transposed, 3 tgmm, the dispatch's transpose as a banded product)
    kernels = re.findall(
        r"%(t?gmm|onto_tokens_banded)[.\d]* = \S+ custom-call\(", text
    )
    assert sorted(kernels) == (
        ["gmm"] * 15 + ["onto_tokens_banded"] * 3 + ["tgmm"] * 6
    )
    assert text.count("tpu_custom_call") == 24
    assert f"[{c},{d}]" in text and f"[{t * k},{d}]" not in text
    # the token side: a [token tile, slab] matrix of weights, never the
    # [tokens, slab] one
    tile = moe._ONTO_TILE
    assert f"[{tile},{c}]" in text or f"[{c},{tile}]" in text
    assert f"[{t},{c}]" not in text and f"[{c},{t}]" not in text
    # both transposes are hand-written: the only scatters are megablox's
    # own, into its [groups + tiles] metadata
    scattered = re.findall(r"= \w+\[([0-9,]*)\]\S* scatter\(", text)
    assert scattered and all("," not in dims for dims in scattered), scattered
    # the loops' bodies under the layer's scopes, as the first slab is
    looped = {
        n for n in re.findall(r'op_name="([^"]+)"', text) if "/while/" in n
    }
    assert {program_trace.scope_of(n) for n in looped} == {
        "moe_dispatch", "moe_experts"
    }
