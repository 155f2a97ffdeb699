"""In-situ decode A/B at the longgen shape (64 slots): attention impl
(kernel vs jnp gather) and kernel grid params (spb/ppcb). Decides where
the per-step floor lives — standalone kernel timings were inconclusive,
so this measures the real engine path."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_flag = "--xla_tpu_scoped_vmem_limit_kib=65536"
if _flag not in os.environ.get("LIBTPU_INIT_ARGS", ""):
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + " " + _flag
    ).strip()

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import JaxGenConfig
    from areal_tpu.inference.engine import GenerationEngine
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.models.transformer import init_params

    cfg = ModelConfig(
        vocab_size=32768, hidden_size=896, intermediate_size=4864,
        num_layers=24, num_heads=14, num_kv_heads=2, head_dim=64,
        max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
        tie_word_embeddings=True, attention_bias=True, family="qwen2",
    )
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    variants = [
        ("kernel ppcb4 spb8 (default)", dict(attn_impl="kernel")),
        ("jnp gather fallback", dict(attn_impl="jnp")),
        ("kernel ppcb8 spb16",
         dict(attn_impl="kernel", pages_per_compute_block=8,
              slots_per_block=16)),
        ("kernel ppcb4 spb16",
         dict(attn_impl="kernel", slots_per_block=16)),
    ]
    mnew = int(os.environ.get("AB_MAX_NEW", "1024"))
    slots = int(os.environ.get("AB_SLOTS", "64"))
    import gc

    for name, kw in variants:
        eng = GenerationEngine(
            JaxGenConfig(
                dtype="bfloat16", max_num_seqs=slots, max_model_len=16384,
                page_size=256, num_pages=1280, prefill_chunk=128,
                decode_chunk=32, decode_pipeline=2, admit_wave=16,
                kv_bucket=2048, **kw,
            ),
            model_config=cfg, params=params,
        ).start()
        try:

            def round_():
                futs = [
                    eng.submit({
                        "input_ids": rng.integers(
                            1, 32768, size=128
                        ).tolist(),
                        "sampling_params": {
                            "max_new_tokens": mnew, "temperature": 1.0,
                        },
                    })
                    for _ in range(slots)
                ]
                t0 = time.perf_counter()
                rs = [f.result(timeout=1800) for f in futs]
                dt = time.perf_counter() - t0
                return sum(len(r["output_ids"]) for r in rs) / dt

            round_(); round_()  # two warmups (bucket ladder)
            rates = [round_() for _ in range(3)]
        finally:
            eng.stop()
            # the engine OBJECT pins its 4 GB pool + params; two variants'
            # pools coexisting would skew (or OOM) the A/B
            del eng
            gc.collect()
        print(
            f"{name:32s} median {sorted(rates)[1]:8.0f} tok/s  "
            f"rounds {[f'{r:.0f}' for r in rates]}",
            flush=True,
        )


if __name__ == "__main__":
    main()
