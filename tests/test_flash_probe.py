"""Splash block probing (ops/flash.probe_block_size): override, fallback
cascade on compile failure, and the refusal when no large edge compiles.
The probe exists because an env-gated block size silently lost the fast
path when the flag didn't take — so its failure behavior is itself
load-bearing."""

import numpy as np
import pytest

import jax

from areal_tpu.ops import flash as F


@pytest.fixture(autouse=True)
def reset_probe():
    prev = F._PROBED_BLOCK
    F._PROBED_BLOCK = None
    F._make_kernel.cache_clear()
    yield
    F._PROBED_BLOCK = prev
    F._make_kernel.cache_clear()


def test_env_override_wins(monkeypatch):
    monkeypatch.setenv("AREAL_TPU_SPLASH_BLOCK", "512")
    assert F.probe_block_size() == 512
    assert F._PROBED_BLOCK == 512


def test_cpu_backend_disables_big_blocks(monkeypatch):
    monkeypatch.delenv("AREAL_TPU_SPLASH_BLOCK", raising=False)
    assert jax.default_backend() == "cpu"
    assert F.probe_block_size() == 0


def test_fallback_cascade_on_compile_failure(monkeypatch):
    """If 2048 fails to compile/run, the probe steps down and keeps the
    largest edge that works; when no edge >= 512 works on a TPU it raises
    with the compiler's message instead of going on with the kernel's
    defaults."""
    monkeypatch.delenv("AREAL_TPU_SPLASH_BLOCK", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    attempts = []

    def fake_attention(q, k, v, seg, window=0):
        attempts.append(F._PROBED_BLOCK)
        if F._PROBED_BLOCK > 512:
            raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem")
        return (q.astype(np.float32) * 0).sum()

    monkeypatch.setattr(F, "flash_segment_attention", fake_attention)
    assert F.probe_block_size() == 512
    assert attempts == [2048, 1024, 512]

    # total failure: every candidate raises -> an error that carries the
    # compiler's words, and nothing cached (a retry probes again)
    F._PROBED_BLOCK = None

    def always_fail(q, k, v, seg, window=0):
        raise RuntimeError("RESOURCE_EXHAUSTED: vmem says no")

    monkeypatch.setattr(F, "flash_segment_attention", always_fail)
    with pytest.raises(RuntimeError, match="vmem says no"):
        F.probe_block_size()
    assert F._PROBED_BLOCK is None


def test_block_size_divisibility():
    """_block_size returns the largest probed-safe edge DIVIDING t."""
    F._PROBED_BLOCK = 1024
    assert F._block_size(16384) == 1024
    assert F._block_size(15360) == 1024  # 15360 % 2048 != 0
    assert F._block_size(1536) == 512
    assert F._block_size(100) == 0  # below the 128 floor
    F._PROBED_BLOCK = 0
    assert F._block_size(16384) == 0
