"""Model correctness: HF-checkpoint parity and packed-vs-padded equivalence.

Mirrors reference test strategy (SURVEY.md §4): packed-vs-padded forward
consistency (areal/tests/test_packed_vs_padded_consistency.py) plus
golden-value parity against the HF torch implementation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import hf_io
from areal_tpu.models.config import ModelConfig, tiny_config
from areal_tpu.models.transformer import apply, init_params
from areal_tpu.utils import data as data_utils


def _hf_tiny_dir(tmp_path, family="qwen2"):
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(0)
    hf_cfg = Qwen2Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
    )
    model = Qwen2ForCausalLM(hf_cfg).eval().to(torch.float32)
    d = tmp_path / "hf_tiny"
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


@pytest.mark.parametrize("seq_len", [17])
def test_qwen2_logits_match_hf(tmp_path, seq_len):
    import torch

    model, path = _hf_tiny_dir(tmp_path)
    cfg = hf_io.load_hf_config(path)
    assert cfg.family == "qwen2" and cfg.attention_bias
    params = hf_io.load_params(path, cfg, dtype=jnp.float32)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, seq_len))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()

    seg = np.ones((1, seq_len), np.int32)
    pos = np.arange(seq_len, dtype=np.int32)[None]
    ours = np.asarray(
        apply(params, cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(seg),
              jnp.asarray(pos), remat=False)
    )
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_packed_matches_padded():
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(1)
    lens = [5, 9, 3]
    seqs = [rng.integers(0, cfg.vocab_size, size=L) for L in lens]

    # per-sequence (padded, one row each) forward
    per_seq_logits = []
    for s in seqs:
        t = jnp.asarray(s, jnp.int32)[None]
        seg = jnp.ones((1, len(s)), jnp.int32)
        pos = jnp.arange(len(s), dtype=jnp.int32)[None]
        per_seq_logits.append(
            np.asarray(apply(params, cfg, t, seg, pos, remat=False))[0]
        )

    # packed single-stream forward with padding tail
    batch = data_utils.pad_sequences_to_tensors(seqs)
    packed = data_utils.pack_batch(batch, pad_to=32)
    logits = np.asarray(
        apply(
            params, cfg,
            jnp.asarray(packed.tokens, jnp.int32)[None],
            jnp.asarray(packed.segment_ids)[None],
            jnp.asarray(packed.positions)[None],
            remat=False,
        )
    )[0]
    off = 0
    for i, L in enumerate(lens):
        np.testing.assert_allclose(
            logits[off : off + L], per_seq_logits[i], rtol=2e-4, atol=2e-4
        )
        off += L


def test_save_load_roundtrip(tmp_path):
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    hf_io.save_params(params, cfg, str(tmp_path / "ckpt"))
    cfg2 = hf_io.load_hf_config(str(tmp_path / "ckpt"))
    assert cfg2.num_layers == cfg.num_layers
    params2 = hf_io.load_params(str(tmp_path / "ckpt"), cfg2, dtype=jnp.float32)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        params, params2,
    )


def test_llama_logits_match_hf(tmp_path):
    """Llama family (no qkv bias, grouped kv) vs HF torch golden — the
    family was previously claimed but only qwen2 was exercised."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(1)
    hf_cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        attention_bias=False,
    )
    model = LlamaForCausalLM(hf_cfg).eval().to(torch.float32)
    d = tmp_path / "hf_llama"
    model.save_pretrained(d, safe_serialization=True)

    cfg = hf_io.load_hf_config(str(d))
    assert cfg.family == "llama" and not cfg.attention_bias
    params = hf_io.load_params(str(d), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, 19))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    seq_len = tokens.shape[1]
    seg = np.ones((1, seq_len), np.int32)
    pos = np.arange(seq_len, dtype=np.int32)[None]
    ours = np.asarray(
        apply(params, cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(seg),
              jnp.asarray(pos), remat=False)
    )
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_qwen3_qk_norm_forward():
    cfg = tiny_config("qwen3")
    assert cfg.qk_norm == "head" and not cfg.attention_bias
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    t = jnp.asarray(np.arange(8)[None] % cfg.vocab_size, jnp.int32)
    seg = jnp.ones((1, 8), jnp.int32)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    out = apply(params, cfg, t, seg, pos, remat=False)
    assert out.shape == (1, 8, cfg.vocab_size)
    assert np.isfinite(np.asarray(out)).all()


def test_gemma_logits_match_hf(tmp_path):
    """Gemma family: GeLU(tanh) MLP, (1+w) RMSNorm, sqrt(d)-scaled
    embeddings — pinned directly against HF GemmaForCausalLM."""
    import torch
    from transformers import GemmaConfig, GemmaForCausalLM

    torch.manual_seed(1)
    hf_cfg = GemmaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=1,  # gemma-2b style MQA
        head_dim=16,
        max_position_embeddings=512,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        hidden_act="gelu_pytorch_tanh",
        hidden_activation="gelu_pytorch_tanh",
        tie_word_embeddings=True,
        attention_bias=False,
    )
    model = GemmaForCausalLM(hf_cfg).eval().to(torch.float32)
    d = tmp_path / "hf_gemma"
    model.save_pretrained(d, safe_serialization=True)

    cfg = hf_io.load_hf_config(str(d))
    assert cfg.family == "gemma"
    assert cfg.hidden_act == "gelu_tanh"
    assert cfg.norm_add_unit_offset and cfg.scale_embeddings
    assert cfg.tie_word_embeddings
    params = hf_io.load_params(str(d), cfg, dtype=jnp.float32)

    rng = np.random.default_rng(2)
    seq_len = 13
    tokens = rng.integers(0, cfg.vocab_size, size=(1, seq_len))
    import torch as _t

    with _t.no_grad():
        ref = model(_t.tensor(tokens)).logits.numpy()
    seg = np.ones((1, seq_len), np.int32)
    pos = np.arange(seq_len, dtype=np.int32)[None]
    ours = np.asarray(
        apply(
            params, cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(seg),
            jnp.asarray(pos), remat=False,
        )
    )
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_gemma_serving_matches_train_forward(tmp_path):
    """The serving runner honors the gemma knobs too: greedy generation
    continuations equal argmax of the training-stack forward."""
    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.config import tiny_config
    from areal_tpu.models.transformer import init_params as init_p

    cfg = tiny_config("gemma")
    params = init_p(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    eng = GenerationEngine(
        JaxGenConfig(
            dtype="float32", max_num_seqs=2, max_model_len=64,
            prefill_chunk=16,
        ),
        model_config=cfg, params=params,
    ).start()
    try:
        prompt = [5, 9, 2, 7]
        out = eng.generate(
            {
                "input_ids": prompt,
                "sampling_params": {"max_new_tokens": 5, "greedy": True},
            }
        )["output_ids"]
    finally:
        eng.stop()
    # teacher-forced argmax with the training stack reproduces the chain
    seq = list(prompt)
    for step in range(5):
        L = len(seq)
        logits = apply(
            params, cfg,
            jnp.asarray([seq], jnp.int32),
            jnp.ones((1, L), jnp.int32),
            jnp.arange(L, dtype=jnp.int32)[None],
            remat=False,
        )
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        assert nxt == out[step], (step, nxt, out)
        seq.append(nxt)
