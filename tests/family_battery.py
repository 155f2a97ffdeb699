"""What every model family's test file says of its family, written once.

A family's file (``test_sparse_attention``, ``test_kimi_linear``,
``test_laguna_swa``, ``test_zaya_cca``, ``test_mellum_swa``) holds a
``Family`` record and the cases that are the family's own; the cases they
share are the functions here. Not collected: imported.

Where the seconds of such a file go is XLA compiling on the CPU, so the
discipline is here, once:

* the plain reference (``benchmark/reference_*.py``: ``jax.numpy`` called
  eagerly, one compiled program an operation and shape) is called under ONE
  ``jax.jit`` a family, at ONE length: the references are causal, so a
  sequence's logits are the first rows of the logits of that sequence
  padded to the family's row (``reference_logits``);
* the program's forward and its gradient are jitted once a family at the one
  row shape ``[1, Family.total]`` (``forward``, ``check_gradients``);
* weights, rows and comparisons are numpy: a slice or a product of a test's
  own compiles nothing;
* one trainer engine a family a module (``trained_fixture``).
"""

import dataclasses
import functools
import json
import os
import sys
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tr
from areal_tpu.models.config import from_hf_config
from areal_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # ``benchmark`` for the family files
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


class Tiny(NamedTuple):
    cfg: Any  # the program's ModelConfig
    keys: dict  # the reference's keys of it
    params: dict  # seeded weights, numpy leaves


@dataclasses.dataclass(frozen=True)
class Family:
    name: str  # ``tiny_config``'s
    tiny_configs: Callable  # () -> (ModelConfig, the reference's keys)
    logits: Callable  # the reference's: (keys, params, tokens) -> [T, V]
    seeded: Optional[Callable] = None  # (cfg, seed) -> weights; plain init
    total: int = 80  # every case's row is [1, total]


def host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


@functools.lru_cache(maxsize=None)
def _init(cfg):
    return jax.jit(lambda key: tr.init_params(cfg, key, jnp.float32))


def init(cfg, seed):
    """Fresh float32 weights of ``cfg``, numpy leaves: one compiled program
    a configuration where the eager draw compiles one a leaf's shape."""
    return host(_init(cfg)(jax.random.PRNGKey(seed)))


def tiny_fixture(fam: Family, seed: int = 4):
    @pytest.fixture(scope="module")
    def tiny():
        cfg, keys = fam.tiny_configs()
        return Tiny(cfg, keys, host((fam.seeded or init)(cfg, seed)))

    return tiny


def row(lens, total, vocab, seed=0):
    """One packed row of seeded sequences of ``lens``, padded to ``total``:
    tokens, segments and positions [1, total], and the sequences."""
    rng = np.random.default_rng(seed)
    tok = np.zeros((1, total), np.int32)
    seg = np.zeros((1, total), np.int32)
    pos = np.zeros((1, total), np.int32)
    seqs, at = [], 0
    for i, n in enumerate(lens):
        s = rng.integers(0, vocab, n).astype(np.int32)
        tok[0, at:at + n], seg[0, at:at + n] = s, i + 1
        pos[0, at:at + n] = np.arange(n)
        seqs.append(s)
        at += n
    return tok, seg, pos, seqs


def layer_of(params, kind, index=0):
    """The weights of layer ``index`` of block ``kind`` (None: a flat stack)."""
    stack = params["layers"][kind] if kind else params["layers"]
    return {k: v[index] for k, v in stack.items()}


def normal(seed, *shape):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32
    )


# ------------------------------------------------------------ the catalog --
def published(name: str) -> dict:
    """The published ``config.json`` of ``name`` in the guide's catalog."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if name in line]
    return next(r for r in rows if r["name"] == name)["config"]


@functools.lru_cache(maxsize=None)
def _cut_text(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return f.read()


def cut(name: str, **changed) -> dict:
    """The keys of ``benchmark/configs/<name>``, with ``changed`` over them."""
    return dict(json.loads(_cut_text(name)), **changed)


def check_refused(name, bad, word):
    with pytest.raises(ValueError, match=word):
        from_hf_config(cut(name, **bad))


# -------------------------------------------------- program and reference --
@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(
        lambda p, tok, seg, pos: tr.apply(
            p, cfg, tok, seg, pos, return_stats=True
        )
    )


def forward(tiny: Tiny, tok, seg, pos, params=None):
    """The program's (logits [1, T, V] as numpy, stats) of one row: one
    compiled program a configuration and row shape."""
    got, stats = _forward(tiny.cfg)(
        tiny.params if params is None else params, tok, seg, pos
    )
    return np.asarray(got), stats


@functools.lru_cache(maxsize=None)
def _reference(fam):
    keys = fam.tiny_configs()[1]
    return jax.jit(lambda p, toks: fam.logits(keys, p, toks))


def reference_logits(fam: Family, tiny: Tiny, seq, params=None):
    """The reference's logits [len(seq), V] of one sequence, numpy. The
    reference runs under one ``jit`` at the row's length: it is causal, so
    what follows a sequence changes nothing before it."""
    toks = np.zeros(fam.total, np.int32)
    toks[:len(seq)] = seq
    out = _reference(fam)(tiny.params if params is None else params, toks)
    return np.asarray(out)[:len(seq)]


def check_logits(fam, tiny, lens, seed=0, atol=5e-5, params=None):
    """The program's logits of ``lens`` packed into the family's row against
    the reference's of each sequence alone. Returns (logits, stats, the
    sequences) for what the family counts."""
    tok, seg, pos, seqs = row(lens, fam.total, tiny.cfg.vocab_size, seed)
    got, stats = forward(tiny, tok, seg, pos, params)
    at = 0
    for s in seqs:
        np.testing.assert_allclose(
            got[0, at:at + len(s)], reference_logits(fam, tiny, s, params),
            atol=atol, rtol=0,
        )
        at += len(s)
    return got, stats, seqs


def check_block(tiny, kind, reference, index=0, carry=None, n=37, atol=2e-5):
    """Layer ``index`` of block ``kind`` on a seeded stream of ``n`` tokens
    (and ``carry`` [n, width] beside it, a block that takes one) against
    ``reference(x, lp)`` / ``reference(x, carry, lp)``, which returns the
    reference's new stream (and carry). Returns the program's stats."""
    cfg = tiny.cfg
    lp = layer_of(tiny.params, kind, index)
    x = normal(1, 1, n, cfg.hidden_size)
    seg = np.ones((1, n), np.int32)
    pos = np.arange(n, dtype=np.int32)[None]
    mixer = kind.split("+")[0]
    state = (x,) if carry is None else (x, carry[None])

    def program(lp, *state):
        return tr._block_body(
            cfg, kind, state[0] if carry is None else state, lp, seg,
            rotation=tr._rotations(cfg, pos).get(mixer),
        )

    got, stats = jax.jit(program)(lp, *state)
    want = jax.jit(reference)(*(s[0] for s in state), lp)
    if carry is None:
        got, want = (got,), (want,)
    for g, w, tol in zip(got, want, (atol, 1e-5)):
        np.testing.assert_allclose(g[0], w, atol=tol, rtol=0)
    return stats


def grpo_loss(seq, seed=8):
    """The GRPO loss of logits [T, V] whose first rows are ``seq``'s: seeded
    old logprobs and advantages, the first four targets masked out."""
    from areal_tpu.ops.functional import ppo_actor_loss_fn

    n = len(seq)
    rng = np.random.default_rng(seed)
    old = (rng.standard_normal(n - 1) * 0.1 - 4.8).astype(np.float32)
    adv = rng.standard_normal(n - 1).astype(np.float32)
    mask = np.arange(n - 1) >= 4
    tgt = np.asarray(seq[1:], np.int32)

    def loss(logits):
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits[:n - 1], axis=-1), tgt[:, None], axis=-1
        )[:, 0]
        return ppo_actor_loss_fn(
            logprobs=logp, old_logprobs=old, advantages=adv, eps_clip=0.2,
            loss_mask=mask, proximal_logprobs=jax.lax.stop_gradient(logp),
            behav_imp_weight_cap=5.0,
        )[0]

    return loss


def check_gradients(
    fam, tiny, loss=grpo_loss, n=41, seed=3, rtol=2e-3, value_tol=(1e-4, 1e-6),
    special=None,
):
    """``loss(seq)(logits)`` of one seeded sequence of ``n`` tokens in the
    family's row and its gradient of every leaf, program against reference,
    each under one ``jit``. ``special``: {leaf name: (g, w) -> (g, w) to
    compare or None to skip, after its own assertions}. Returns {leaf name:
    the reference gradient's largest entry} of the leaves compared."""
    cfg = tiny.cfg
    tok, seg, pos, (seq,) = row([n], fam.total, cfg.vocab_size, seed)
    fn = loss(seq)
    got_v, got = jax.jit(jax.value_and_grad(
        lambda p: fn(tr.apply(p, cfg, tok, seg, pos, remat=True)[0])
    ))(tiny.params)
    want_v, want = jax.jit(jax.value_and_grad(
        lambda p: fn(fam.logits(tiny.keys, p, tok[0]))
    ))(tiny.params)
    got_v, want_v = float(got_v), float(want_v)
    assert abs(got_v - want_v) < value_tol[0] * abs(want_v) + value_tol[1]
    flat_got = jax.tree_util.tree_leaves_with_path(host(got))
    flat_want = jax.tree_util.tree_leaves(host(want))
    assert len(flat_got) == len(flat_want)
    seen = {}
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        leaf = name.split("'")[-2]
        if special and leaf in special:
            pair = special[leaf](g, w)
            if pair is None:
                continue
            g, w = pair
        scale = float(np.abs(w).max())
        assert scale > 0, name
        assert float(np.abs(g - w).max()) <= rtol * scale, name
        seen[leaf] = scale
    return seen


def check_window_edge(form, t, window, s, reference):
    """The key at distance window - 1 is seen, the one at distance window is
    not: with v replaced at one key ``s``, the outputs of queries s .. s +
    window - 1 move and every other row is bit-for-bit what it was, in the
    XLA form and in the splash kernel (interpret mode); and the form agrees
    with ``reference``, the family's masked softmax."""
    from areal_tpu.ops import basic
    from areal_tpu.ops.flash import flash_segment_attention

    q, k, v = (normal(i, 1, t, hh, 64) for i, hh in enumerate((4, 2, 2)))
    seg = np.ones((1, t), np.int32)
    if form == "xla":
        attend = jax.jit(lambda v: basic.segment_attention(
            q, k, v, seg, window=window
        ))
    else:
        attend = jax.jit(lambda v: flash_segment_attention(
            q, k, v, seg, layer_window=window, interpret=True
        ))
    base = np.asarray(attend(v))
    v2 = v.copy()
    v2[0, s] += 3.0
    changed = np.abs(np.asarray(attend(v2)) - base).max(axis=(2, 3))[0] > 0
    assert changed[s:s + window].all()
    assert not changed[:s].any() and not changed[s + window:].any()
    want = jax.jit(lambda q, k, v: reference(q, k, v, window))(
        q[0], k[0], v[0]
    )
    np.testing.assert_allclose(base[0], want, atol=2e-5)


# ------------------------------------------------ the share of the experts --
@functools.lru_cache(maxsize=None)
def _whole(fam, cfg, seed):
    whole = dataclasses.replace(cfg, experts_held=0)
    return whole, host((fam.seeded or init)(whole, seed))


def whole(fam, tiny, seed=9):
    """(the tiny configuration holding EVERY expert, seeded weights of it)."""
    return _whole(fam, tiny.cfg, seed)


@functools.partial(jax.jit, static_argnames=("cfg", "first"))
def share_part(cfg, h, lp, held, first, router_bias=None):
    """(the part of the sparse layer's output that the holder of experts
    ``first``.. computes, its stats): ``held`` are its experts' weights."""
    return moe_ops.moe_ffn(
        h, lp["w_router"], *held, cfg.num_experts_per_tok, cfg.norm_topk_prob,
        first_expert=first, router_act=cfg.router_act, router_bias=router_bias,
        routed_scaling_factor=cfg.routed_scaling_factor,
    )


def holding(lp, first, n):
    """``lp`` with the experts ``first`` .. ``first + n - 1`` only."""
    held = slice(first, first + n)
    return dict(lp, **{k: lp[k][held] for k in EXPERT_LEAVES})


def check_shares_add_up(
    fam, tiny, kind, experts, shared=None, router_bias=None, n_tokens=29,
):
    """What ties the share to the model: the holders of ``num_held_experts``
    each route over all the experts with the same router; their parts (and
    the shared expert, counted ONCE) add up to the uncut reference's layer,
    and the layer's own call is the first holder's part. ``experts(keys, h,
    lp)`` and ``shared(h, lp)`` are the reference's."""
    cfg, keys = tiny.cfg, tiny.keys
    _, params = whole(fam, tiny)
    lp = layer_of(params, kind)
    if router_bias is not None:
        lp["router_bias"] = router_bias
    n, held_n = cfg.num_experts, cfg.num_held_experts
    h = normal(5, 1, n_tokens, cfg.hidden_size)

    @jax.jit
    def reference(h, lp, mine):
        extra = shared(h, lp) if shared else 0.0
        return (
            experts(dict(keys, num_local_experts=n), h, lp) + extra,
            experts(keys, h, mine) + extra,
        )

    mine = holding(lp, 0, held_n)
    want, first_part = reference(h[0], lp, mine)
    total = 0.0
    if shared:
        total = np.asarray(jax.jit(
            lambda lp, h: moe_ops.shared_expert_from_params(cfg, lp, h)
        )(lp, h))[0]
    pairs = 0
    for first in range(0, n, held_n):
        part, stats = share_part(
            cfg, h, lp, [lp[k][first:first + held_n] for k in EXPERT_LEAVES],
            first, lp.get("router_bias") if router_bias is not None else None,
        )
        total = total + np.asarray(part)[0]
        pairs += int(stats.assignments)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=0)
    assert pairs == n_tokens * cfg.num_experts_per_tok
    own, _ = jax.jit(
        lambda lp, h: moe_ops.moe_ffn_from_params(cfg, lp, h)
    )(mine, h)
    np.testing.assert_allclose(own[0], first_part, atol=1e-5, rtol=0)


# ------------------------------------------------------------ the refusals --
def check_refusals(fam, tiny, tmp_path, generation, save, seq_parallel):
    """The generation engine, a checkpoint of a share's layout and a
    sequence-parallel mesh refuse the family, each by the word given."""
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models import hf_io
    from areal_tpu.ops.flash import resolve_attn_impl

    cfg = tiny.cfg
    with pytest.raises(NotImplementedError, match=generation):
        GenerationEngine(JaxGenConfig(), model_config=cfg, params=tiny.params)
    with pytest.raises(NotImplementedError, match=save):
        hf_io.save_params(*reversed(whole(fam, tiny)), str(tmp_path))
    for impl, sp in (("ring", 1), ("ulysses", 2), ("auto", 2)):
        with pytest.raises(ValueError, match=seq_parallel):
            resolve_attn_impl(
                impl, sp, (16, 16), *tr.seq_parallel_refusal(cfg)
            )
    assert resolve_attn_impl(
        "auto", 1, (16, 16), *tr.seq_parallel_refusal(cfg)
    ) == "xla"


# -------------------------------------------------------------- the trainer --
TRAIN_LENS = (30, 12, 44, 9, 25, 18, 40, 7)


@dataclasses.dataclass
class Trained:
    """One GRPO update of the family's tiny model by the shipped recipe."""

    engine: Any
    lens: tuple
    before: dict  # the layers' weights before the update, numpy
    stats: List[dict]  # ``ppo_update``'s, a minibatch each
    spans: list  # (name, attrs) of every span the update opened
    exported: dict  # ``stats_tracker.export_all()`` of the update

    def attrs(self, name):
        found = [a for n, a in self.spans if n == name]
        assert found, name
        return found

    def moved(self, kind, *names):
        """Every named leaf of ``kind`` (None: a flat stack) was trained."""
        after = self.engine.params["layers"]
        after, was = (after, self.before) if kind is None else (
            after[kind], self.before[kind]
        )
        for name in names:
            assert np.abs(np.asarray(after[name]) - was[name]).max() > 0, name


def train(cfg, prepare=None) -> Trained:
    from areal_tpu.api import cli_args
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import PPOActor
    from areal_tpu.engine.spmd_engine import SPMDTrainEngine
    from areal_tpu.utils import data as data_utils, stats_tracker, tracing

    full, _ = cli_args.load_expr_config(
        ["--config", os.path.join(ROOT, "examples", "gsm8k_grpo.yaml")],
        cli_args.GRPOConfig,
    )
    acfg = full.actor  # the recipe as shipped, no option set
    acfg.path, acfg.dtype = "", "float32"
    engine = SPMDTrainEngine(acfg)
    engine.initialize(
        ft_spec=FinetuneSpec(1, 1000, 8), model_config=cfg, seed=3
    )
    if prepare:
        prepare(engine)
    actor = PPOActor(acfg, engine)
    rng = np.random.default_rng(12)
    lens = TRAIN_LENS
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in lens]
    batch = data_utils.pad_sequences_to_tensors(seqs)
    lm = batch["attention_mask"].astype(np.int32)
    lm[:, :3] = 0
    batch["loss_mask"] = lm
    batch["logprobs"] = (
        rng.standard_normal(lm.shape).astype(np.float32) * 0.1 - 4.8
    ) * lm
    batch["versions"] = np.where(lm > 0, 0, -1).astype(np.int32)
    batch["rewards"] = np.tile(np.array([0, 1], np.float32), 4)
    before = host(engine.params["layers"])
    spans, span = [], tracing.span

    def recording(name, *a, **attrs):
        spans.append((name, attrs))
        return span(name, *a, **attrs)

    with pytest.MonkeyPatch.context() as patch:
        # rows of 256 tokens for the batch's 185 (the ladder's step is 2048)
        patch.setattr(data_utils, "ROW_QUANTUM", 256)
        patch.setattr(tracing, "span", recording)
        stats_tracker.export_all()
        stats = actor.ppo_update(actor.compute_advantages(batch))
        exported = stats_tracker.export_all()
    assert all(s["update_successful"] == 1.0 for s in stats)
    return Trained(engine, lens, before, stats, spans, exported)


def trained_fixture(prepare=None):
    """The module's one trainer engine, after one update of ``tiny``'s model
    (``prepare(engine)`` runs between its initialisation and the update)."""

    @pytest.fixture(scope="module")
    def trained(tiny):
        out = train(tiny.cfg, prepare)
        yield out
        out.engine.destroy()

    return trained
