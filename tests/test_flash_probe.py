"""Splash block probing (ops/flash.probe_block_size): fallback cascade on
compile failure and the refusal when no large edge compiles — the probe
exists because a scoped-VMEM flag that did not take silently lost the fast
path, so its failure behavior is itself load-bearing — and the tiles
``instance`` chooses from the shape under the cap it is handed."""

import pytest

import jax

from areal_tpu.ops import flash as F


@pytest.fixture(autouse=True)
def reset_probe():
    F.probe_block_size.cache_clear()
    yield
    F.probe_block_size.cache_clear()


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
    assert F.probe_block_size() == 512 and len(attempts) == 3  # memoised
    # and the tiles of a caller that was handed no cap stay the rule's own:
    # the probe's answer is a value, not a state of the module
    assert F.instance(16384, 128, heads=8).edge == 2048

    # total failure: every candidate raises -> an error that carries the
    # compiler's words, and nothing memoised (a retry probes again)
    F.probe_block_size.cache_clear()
    monkeypatch.setattr(
        F._sk, "make_splash_mqa_single_device",
        _refusing_above(0, attempts, "RESOURCE_EXHAUSTED: vmem says no"),
    )
    with pytest.raises(RuntimeError, match="vmem says no"):
        F.probe_block_size()
    n = len(attempts)
    with pytest.raises(RuntimeError, match="vmem says no"):
        F.probe_block_size()
    assert len(attempts) == n + 3  # 2048, 1024, 512 again


@pytest.mark.parametrize(
    "d,taken",
    [(64, True), (128, True), (256, True), (16, False), (80, False),
     (96, False), (0, False)],
)
def test_head_dimensions_the_trainer_hands_to_the_kernel(d, taken):
    assert F.takes_head_dim(d) is taken


# (T, the cap handed in, 0 = none) -> the edge of every tile
_EDGES = [
    pytest.param(4096, 2048, 1024, id="cell-row-full-causal"),
    pytest.param(16384, 2048, 2048, id="recipe-row"),
    pytest.param(16384, 1024, 1024, id="recipe-row-capped-by-probe"),
    pytest.param(16384, 0, 2048, id="no-cap-is-uncapped"),
    pytest.param(15360, 2048, 1024, id="edge-divides-the-row"),
    pytest.param(8192, 2048, 1024, id="8192-takes-1024"),
    pytest.param(2048, 2048, 512, id="short-rung-takes-512"),
    pytest.param(32768, 2048, 2048, id="past-16384-takes-2048-split"),
    pytest.param(1536, 2048, 512, id="short-row-divisor"),
    pytest.param(256, 0, 256, id="cpu-cap-0-is-no-cap"),
    pytest.param(384, 2048, 128, id="odd-multiple-of-128"),
    pytest.param(4096, 512, 512, id="cell-row-capped-at-512"),
    pytest.param(32768, 1024, 1024, id="long-row-capped-at-1024"),
]


@pytest.mark.parametrize("t,cap,edge", _EDGES)
def test_tiles_follow_the_shape_under_the_cap_handed_in(t, cap, edge):
    inst = F.instance(t, 64, block_cap=cap, heads=2)
    # heads of 64 or 128 move nothing (two of them: no partial dq to weigh)
    assert inst == F.instance(t, 128, block_cap=cap, heads=2)
    assert inst == ("splash", 0, edge, True, min(512, edge)) and t % edge == 0
    # 32 heads of 128: the fused backward to 16,384 tokens at the chip's cap
    fits = t // edge * 32 * t * 128 * 2 <= 2.5 * (1 << 30)
    assert F.instance(t, 128, block_cap=cap or 2048, heads=32) == (
        inst._replace(fused=fits)
    )
    tiles = F._tiles(inst)
    assert {tiles.block_q, tiles.block_kv, tiles.block_q_dkv,
            tiles.block_kv_dkv} == {edge}
    assert tiles.block_kv_compute == tiles.block_kv_dkv_compute == min(512, edge)
    assert tiles.use_fused_bwd_kernel == inst.fused
