"""Splash block probing (ops/flash.probe_block_size): fallback cascade on
compile failure and the refusal when no large edge compiles — the probe
exists because a scoped-VMEM flag that did not take silently lost the fast
path, so its failure behavior is itself load-bearing — and the tiles
``_block_sizes`` chooses from the shape under the probed cap."""

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


def test_cpu_backend_has_nothing_to_cap():
    assert jax.default_backend() == "cpu"
    assert F.probe_block_size() == 0


def _refusing_above(limit, attempts, message):
    """A kernel factory whose kernels fail the way an edge the scoped-VMEM
    limit cannot hold does: when first run, above ``limit``."""

    def make(mask, block_sizes=None, **kw):
        def kernel(q, k, v):
            attempts.append(block_sizes.block_q)
            if block_sizes.block_q > limit:
                raise RuntimeError(message)
            return q * 0

        return kernel

    return make


def test_fallback_cascade_on_compile_failure(monkeypatch):
    """If 2048 fails to compile/run, the probe steps down and keeps the
    largest edge that works; when no edge >= 512 works on a TPU it raises
    with the compiler's message instead of going on with small tiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attempts = []
    monkeypatch.setattr(
        F._sk, "make_splash_mqa_single_device",
        _refusing_above(512, attempts, "RESOURCE_EXHAUSTED: scoped vmem"),
    )
    assert F.probe_block_size() == 512
    assert attempts == [2048, 1024, 512]
    assert F.probe_block_size() == 512 and len(attempts) == 3  # cached

    # total failure: every candidate raises -> an error that carries the
    # compiler's words, and nothing cached (a retry probes again)
    F._PROBED_BLOCK = None
    monkeypatch.setattr(
        F._sk, "make_splash_mqa_single_device",
        _refusing_above(0, attempts, "RESOURCE_EXHAUSTED: vmem says no"),
    )
    with pytest.raises(RuntimeError, match="vmem says no"):
        F.probe_block_size()
    assert F._PROBED_BLOCK is None


@pytest.mark.parametrize(
    "d,taken",
    [(64, True), (128, True), (256, True), (16, False), (80, False),
     (96, False), (0, False)],
)
def test_head_dimensions_the_trainer_hands_to_the_kernel(d, taken):
    assert F.takes_head_dim(d) is taken


# (T, window, probed cap or None) -> the edge of every tile
_EDGES = [
    pytest.param(4096, 0, 2048, 1024, id="cell-row-full-causal"),
    pytest.param(4096, 2048, 2048, 1024, id="cell-row-window2048"),
    pytest.param(4096, 1024, 2048, 512, id="window1024-takes-512"),
    pytest.param(16384, 0, 2048, 2048, id="recipe-row"),
    pytest.param(16384, 0, 1024, 1024, id="recipe-row-capped-by-probe"),
    pytest.param(16384, 4096, None, 2048, id="unprobed-is-uncapped"),
    pytest.param(15360, 0, 2048, 1024, id="edge-divides-the-row"),
    pytest.param(8192, 0, 2048, 1024, id="8192-takes-1024"),
    pytest.param(2048, 0, 2048, 512, id="short-rung-takes-512"),
    pytest.param(32768, 0, 2048, 2048, id="past-16384-takes-2048-split"),
    pytest.param(1536, 0, 2048, 512, id="short-row-divisor"),
    pytest.param(256, 0, 0, 256, id="cpu-cap-0-is-no-cap"),
    pytest.param(384, 0, 2048, 128, id="odd-multiple-of-128"),
]


@pytest.mark.parametrize("t,window,cap,edge", _EDGES)
def test_tiles_follow_the_shape_under_the_probed_cap(t, window, cap, edge):
    F._PROBED_BLOCK = cap
    tiles = F._block_sizes(t, window, 64)
    assert tiles == F._block_sizes(t, window, 128)  # head dim moves nothing
    assert {tiles.block_q, tiles.block_kv, tiles.block_q_dkv,
            tiles.block_kv_dkv} == {edge}
    assert tiles.block_kv_compute == tiles.block_kv_dkv_compute == min(512, edge)
    assert tiles.use_fused_bwd_kernel == (t <= 16384) and t % edge == 0
