"""Hierarchical KV tiers (r16): host-RAM spill under the radix tree,
claim-time promotion, disk overflow, and cross-server prefix shipping.

Tentpole invariants:

- **Demotion is lossless**: a page demoted to the host tier and later
  promoted back is bit-identical — the spill tier changes WHERE cached
  KV lives, never its content. Greedy streams are bit-identical with
  kv_spill on vs off even when the device pool thrashes (engine-level
  parity test, slow).
- **Strict no-op off**: kv_spill off emits zero kv_tier_* metric keys
  and the tree behaves exactly as r9 (covered by the pre-existing radix
  suite running tierless).
- **Refcount conservation across tiers**: demotion releases exactly the
  tree's reference; promotion allocates exactly one page whose single
  reference is the tree's; pending-promotion cancellation returns the
  page untouched. Pages shared with live claimants are never cancelled
  (the flush they are waiting on must happen).
- **Shipping enters through publish/claim**: an imported prefix becomes
  ordinary radix-tree state — the canonical [L, Hkv, tokens, D] form
  makes pages portable across pool layouts.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.cli_args import JaxGenConfig
from areal_tpu.inference.cache import PageManager, RadixPrefixCache
from areal_tpu.inference.engine import GenerationEngine
from areal_tpu.inference.kv_tiers import (
    KvTierManager,
    canonical_from_pool,
    pool_from_canonical,
    resolve_np_dtype,
)
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import init_params

BS = 8  # page size for host-level tests


# ---------------------------------------------------------------------------
# Canonical page form (the shipping/portability contract)
# ---------------------------------------------------------------------------
def test_canonical_roundtrip_both_layouts():
    rng = np.random.default_rng(0)
    nl, hkv, d = 2, 2, 4
    t = 16  # 4 pages of 4 tokens in both geometries below
    canon = rng.standard_normal((nl, hkv, t, d)).astype(np.float32)
    # token-packed: Hp=Hkv, lane = f*D with f=2, rows=2 → 4 tokens/page
    tp_shape = (nl, hkv, 4, 2, 2 * d)
    tp = pool_from_canonical(canon, tp_shape)
    assert tp.shape == tp_shape
    np.testing.assert_array_equal(canonical_from_pool(tp, hkv, d), canon)
    # head-merged: Hp=1, lane = f'*Hkv*D with f'=1, rows=4 → 4 tokens/page
    hm_shape = (nl, 1, 4, 4, hkv * d)
    hm = pool_from_canonical(canon, hm_shape)
    assert hm.shape == hm_shape
    np.testing.assert_array_equal(canonical_from_pool(hm, hkv, d), canon)
    # cross-layout transfer: packed pool → canonical → merged pool →
    # canonical survives — the portability claim shipping relies on
    via = canonical_from_pool(
        pool_from_canonical(canonical_from_pool(tp, hkv, d), hm_shape),
        hkv, d,
    )
    np.testing.assert_array_equal(via, canon)


def test_resolve_np_dtype_covers_ml_dtypes():
    assert resolve_np_dtype("float32") == np.float32
    bf16 = resolve_np_dtype("bfloat16")
    assert bf16.itemsize == 2 and bf16.name == "bfloat16"


# ---------------------------------------------------------------------------
# Host-level tier semantics (fake device pool: numpy arrays + a gather
# closure; "scatter" applies drain_pending by hand)
# ---------------------------------------------------------------------------
class _FakePool:
    """Numpy stand-in for the paged device pool: [L, H, NP, rows, lane]
    with per-page distinctive content, a KvTierManager-compatible
    gather, and a drain-applying scatter."""

    def __init__(self, num_pages: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.k = rng.standard_normal((2, 2, num_pages, 2, 16)).astype(
            np.float32
        )
        self.v = rng.standard_normal((2, 2, num_pages, 2, 16)).astype(
            np.float32
        )

    def gather(self, pages):
        idx = np.asarray(pages, np.int32)
        return (
            np.ascontiguousarray(self.k[:, :, idx]),
            np.ascontiguousarray(self.v[:, :, idx]),
        )

    def apply(self, pending):
        for page, sp in pending:
            self.k[:, :, page] = sp.k
            self.v[:, :, page] = sp.v


def _tiered(pm_pages=16, host_bytes=1 << 20, disk_path="", **tree_kw):
    pm = PageManager(pm_pages)
    tree = RadixPrefixCache(BS, min_match=4, **tree_kw)
    pool = _FakePool(pm_pages)
    tiers = KvTierManager(
        host_bytes=host_bytes, gather_fn=pool.gather, disk_path=disk_path
    )
    tree.attach_tiers(tiers)
    return pm, tree, pool, tiers


def test_demote_promote_roundtrip_bit_identical():
    pm, tree, pool, tiers = _tiered(pm_pages=8)
    tokens = np.arange(16, dtype=np.int32)  # 2 full pages
    pages = pm.alloc(2)
    snap_k = pool.k[:, :, pages].copy()
    tree.add(pm, tokens, pages)  # ownership transfer: tree sole holder
    assert all(pm.refcount[p] == 1 for p in pages)
    free0 = pm.n_free
    # eviction pressure → demotion, not drop
    got = tree.evict(pm, free0 + 2)
    assert got == 2 and pm.n_free == free0 + 2
    assert len(tree) == 2 and tree.pages == 0  # nodes stay, spilled
    assert tiers.host_pages == 2
    assert tiers.spilled_pages_total == 2
    # overwrite the freed device pages (the pool reuses them)
    pool.k[:, :, pages] = -1.0
    # claim descends through the spilled nodes → promotion
    shared, off, src, cow = tree.claim_cow(
        pm, list(range(16)) + [99]
    )
    assert off == 16 and len(shared) == 2 and src is None
    assert tiers.pending_pages == 2 and tiers.last_claim_promoted == 2
    assert tiers.claims_promoted_total == 1
    # tree ref + claimant ref on each fresh page
    assert all(pm.refcount[p] == 2 for p in shared)
    # the engine's flush: one batched scatter of the drained queue
    pend = tiers.drain_pending()
    assert sorted(p for p, _ in pend) == sorted(shared)
    pool.apply(pend)
    np.testing.assert_array_equal(pool.k[:, :, shared], snap_k)
    assert tiers.pending_pages == 0
    assert tiers.promoted_pages_total == 2 and tiers.host_pages == 0
    pm.release(shared)
    assert all(pm.refcount[p] == 1 for p in shared)


def test_host_budget_lru_drops_to_hole():
    # budget fits exactly one spilled page → the LRU entry drops and its
    # node becomes a hole; a claim reaching the hole stops there
    pm, tree, pool, tiers = _tiered(pm_pages=8)
    pages = pm.alloc(2)
    tree.add(pm, np.arange(16, dtype=np.int32), pages)
    # learn the page size from a first demotion, then shrink the budget
    tree.evict(pm, pm.n_free + 2)
    assert tiers.host_pages == 2
    one_page = tiers._page_nbytes
    tiers.host_capacity = one_page
    tiers._enforce_host_budget()
    assert tiers.host_pages == 1 and tiers.dropped_pages_total == 1
    # demotion is leaf-first, so the LRU host entry (dropped) is the
    # LEAF page: the hole forms at depth 1 and a claim promotes the
    # surviving depth-0 page, then stops at the hole
    shared, off, src, cow = tree.claim_cow(pm, list(range(16)) + [99])
    assert off == 8 and len(shared) == 1 and src is None
    assert tiers.pending_pages == 1
    # match_pages (the export path) also stops at the hole
    assert len(tree.match_pages(np.arange(16, dtype=np.int32))) == 1
    pool.apply(tiers.drain_pending())
    pm.release(shared)


def test_pending_promotion_cancel_and_claimant_protection():
    pm, tree, pool, tiers = _tiered(pm_pages=6)
    pages = pm.alloc(2)
    tree.add(pm, np.arange(16, dtype=np.int32), pages)
    tree.evict(pm, pm.n_free + 2)  # both pages host-side
    shared, off, _, _ = tree.claim_cow(pm, list(range(16)) + [99])
    assert off == 16 and tiers.pending_pages == 2
    # eviction pressure BEFORE the flush: pending pages are claimant-
    # shared (refcount 2) → they must NOT be cancelled out from under
    # the claimant (it is waiting on the scatter to make them real)
    tree.evict(pm, pm.n_free + 1)
    assert tiers.pending_pages == 2
    assert all(pm.refcount[p] == 2 for p in shared)
    # release the claim (wave deferred) — now the tree is sole holder
    # and cancellation is legal: page returns untouched, copy re-files
    pm.release(shared)
    tree.evict(pm, pm.n_free + 2)
    assert tiers.pending_pages == 0 and tiers.host_pages == 2
    assert pm.refcount[shared[0]] == 0 and pm.refcount[shared[1]] == 0
    # the re-filed copies still promote cleanly
    shared2, off2, _, _ = tree.claim_cow(pm, list(range(16)) + [99])
    assert off2 == 16
    pool.apply(tiers.drain_pending())
    pm.release(shared2)


def test_disk_tier_roundtrip(tmp_path):
    disk = str(tmp_path / "kv")
    pm, tree, pool, tiers = _tiered(pm_pages=8, disk_path=disk)
    pages = pm.alloc(2)
    snap_k = pool.k[:, :, pages].copy()
    tree.add(pm, np.arange(16, dtype=np.int32), pages)
    tree.evict(pm, pm.n_free + 2)
    one_page = tiers._page_nbytes
    tiers.host_capacity = one_page  # overflow → disk, not drop
    tiers._enforce_host_budget()
    assert tiers.host_pages == 1 and tiers.disk_pages == 1
    assert tiers.dropped_pages_total == 0
    assert len(os.listdir(disk)) == 1
    # promotion loads the file back and deletes it
    shared, off, _, _ = tree.claim_cow(pm, list(range(16)) + [99])
    assert off == 16 and tiers.disk_loaded_pages_total == 1
    pool.apply(tiers.drain_pending())
    np.testing.assert_array_equal(pool.k[:, :, shared], snap_k)
    assert len(os.listdir(disk)) == 0
    pm.release(shared)
    # flush clears every tier and deletes stray files
    tree.flush(pm)
    assert tiers.host_pages == 0 and tiers.disk_pages == 0
    assert pm.n_free == pm.num_pages


def test_publish_adoption_heals_spilled_node():
    # a prefill re-commits tokens whose node is spilled: publish adopts
    # the freshly-written page and forgets the stale host copy
    pm, tree, pool, tiers = _tiered(pm_pages=8)
    pages = pm.alloc(2)
    tree.add(pm, np.arange(16, dtype=np.int32), pages)
    tree.evict(pm, pm.n_free + 2)
    assert tree.pages == 0 and tiers.host_pages == 2
    fresh = pm.alloc(2)
    ins = tree.publish(pm, np.arange(16, dtype=np.int32), fresh)
    assert ins == 2 and tree.pages == 2
    assert tiers.host_pages == 0  # stale copies forgotten
    # publish is non-owning: caller keeps its refs, tree added its own
    assert all(pm.refcount[p] == 2 for p in fresh)
    pm.release(fresh)
    shared, off, _, _ = tree.claim_cow(pm, list(range(16)) + [99])
    assert off == 16 and tiers.pending_pages == 0  # plainly resident
    pm.release(shared)


def test_match_pages_reads_without_side_effects():
    pm, tree, pool, tiers = _tiered(pm_pages=8)
    pages = pm.alloc(2)
    tree.add(pm, np.arange(16, dtype=np.int32), pages)
    tree.evict(pm, pm.n_free + 1)  # spill the leaf only
    claims0 = tree.claims
    nodes = tree.match_pages(np.arange(16, dtype=np.int32))
    assert len(nodes) == 2
    assert nodes[0].page is not None and nodes[1].spill is not None
    # no refcount, LRU, or counter effects
    assert tree.claims == claims0
    assert pm.refcount[nodes[0].page] == 1
    k, v = tiers.export_data(nodes[1])
    assert k.shape == (2, 2, 2, 16)  # one page, still host-resident
    assert tiers.host_pages == 1


# ---------------------------------------------------------------------------
# Engine-level: metrics gating, promotion parity, shipping
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = tiny_config("qwen2")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


@pytest.fixture()
def engine_factory(model):
    cfg, params = model
    engines = []

    def make(**kw):
        kw.setdefault("page_size", 16)
        kw.setdefault("max_num_seqs", 8)
        kw.setdefault("max_model_len", 128)
        gcfg = JaxGenConfig(
            dtype="float32", prefill_chunk=16, admit_hold_s=0.0, **kw,
        )
        eng = GenerationEngine(gcfg, model_config=cfg, params=params).start()
        engines.append(eng)
        return eng

    yield make
    for e in engines:
        e.stop()


def _greedy(eng, prompt, n=8):
    return eng.generate({
        "input_ids": [int(t) for t in prompt],
        "sampling_params": {"max_new_tokens": n, "greedy": True},
    })


def test_metric_surface_gated_on_flags(engine_factory):
    base = engine_factory(prefix_reuse_min=8)
    m0 = set(base.metrics())
    assert not any(k.startswith(("kv_tier_", "kv_ship_")) for k in m0)
    spill = engine_factory(prefix_reuse_min=8, kv_spill=True, kv_ship=True)
    m1 = set(spill.metrics())
    assert {"kv_tier_host_pages", "kv_tier_spilled_pages_total",
            "kv_tier_host_claim_hit_rate", "kv_ship_exports_total",
            "kv_ship_failures_total"} <= m1
    # spill on adds ONLY kv_tier_*/kv_ship_* keys — nothing else moves
    assert {k for k in m1 - m0} == {
        k for k in m1 if k.startswith(("kv_tier_", "kv_ship_"))
    }


def test_kv_spill_requires_radix(model):
    cfg, params = model
    with pytest.raises(ValueError, match="radix"):
        GenerationEngine(
            JaxGenConfig(
                dtype="float32", page_size=16, max_num_seqs=4,
                max_model_len=64, kv_spill=True,
                prefix_cache_mode="flat",
            ),
            model_config=cfg, params=params,
        )


def test_spill_promotion_serves_returning_session(engine_factory):
    """Thrash the pool so a finished session's pages demote, then
    return with the same prefix: the claim must be served from the
    host tier (promotion), not a re-prefill."""
    eng = engine_factory(
        prefix_reuse_min=16, kv_spill=True, num_pages=24, admit_wave=1,
    )
    rng = np.random.default_rng(1)
    keep = list(rng.integers(1, 128, size=48))
    _greedy(eng, keep, n=4)
    # churn: distinct prompts until eviction demotes keep's pages. Each
    # parks 3 full pages of a pool of 24, so a handful do; the bound is a
    # count of prompts and not seconds, which a busy machine runs out of
    for _ in range(32):
        if eng.metrics().get("kv_tier_spilled_pages_total", 0):
            break
        _greedy(eng, list(rng.integers(1, 128, size=48)), n=4)
    else:
        pytest.fail("pool churn never demoted")
    # the session returns: same prompt prefix, one more turn
    out = _greedy(eng, keep, n=4)
    m = eng.metrics()
    assert m["kv_tier_promoted_pages_total"] > 0
    assert m["kv_tier_host_claim_hits_total"] >= 1
    assert m["kv_tier_host_cached_tokens_total"] >= 16
    assert out["meta_info"]["cached_tokens"] >= 16


@pytest.mark.slow
def test_greedy_parity_spill_on_off_under_thrash(engine_factory):
    """Greedy streams bit-identical with kv_spill on vs off while the
    device pool thrashes — promotion restores exact page contents."""
    # 48-token prompts → 3 FULL pages each once parked (tails are
    # removed, not spilled, so only full pages exercise the tier); a
    # 16-page pool cannot hold 6×3 parked pages → eviction every lap
    prompts = [
        list(np.random.default_rng(s).integers(1, 128, size=48))
        for s in range(6)
    ]

    def run(**kw):
        eng = engine_factory(
            prefix_reuse_min=16, num_pages=16, admit_wave=1, **kw
        )
        outs = []
        for rep in range(2):  # second lap returns to evicted prefixes
            for p in prompts:
                r = _greedy(eng, p, n=6)
                if r["meta_info"].get("preemptions", 0) == 0:
                    outs.append((tuple(p), rep, r["output_ids"]))
        return outs, eng.metrics()

    base, _ = run(kv_spill=False)
    spill, m = run(kv_spill=True)
    assert m["kv_tier_spilled_pages_total"] > 0, "no demotion: test inert"
    base_map = {(p, rep): out for p, rep, out in base}
    spill_map = {(p, rep): out for p, rep, out in spill}
    common = set(base_map) & set(spill_map)
    assert len(common) >= len(prompts)  # enough overlap to mean something
    for key in common:
        assert base_map[key] == spill_map[key], key


def test_export_import_roundtrip_two_engines(engine_factory):
    """The shipping pair without HTTP: engine A exports a committed
    prefix, engine B imports it and serves the next turn cached."""
    a = engine_factory(prefix_reuse_min=16, kv_ship=True, admit_wave=1)
    b = engine_factory(prefix_reuse_min=16, kv_ship=True, admit_wave=1)
    prompt = list(np.random.default_rng(7).integers(1, 128, size=48))
    ra = _greedy(a, prompt, n=6)
    full = prompt + ra["output_ids"]
    out = a.export_prefix(full)
    assert out["pages"] >= 3 and out["tokens_matched"] >= 48
    assert a.metrics()["kv_ship_exports_total"] == 1
    n = b.import_prefix(
        full[: out["tokens_matched"]], out["k"], out["v"],
        src_version=out["model_version"],
    )
    assert n == out["tokens_matched"]
    assert b.metrics()["kv_ship_pages_in_total"] == out["pages"]
    # B serves the next turn from the shipped pages — and produces the
    # same continuation A would (the shipped KV is bit-faithful)
    rb = _greedy(b, full, n=6)
    assert rb["meta_info"]["cached_tokens"] >= out["tokens_matched"] - 16
    rb2 = _greedy(a, full, n=6)
    assert rb["output_ids"] == rb2["output_ids"]
    # version mismatch soft-fails
    assert b.import_prefix(full[:16], out["k"], out["v"],
                           src_version=999) == 0
    assert b.metrics()["kv_ship_failures_total"] == 1


# ---------------------------------------------------------------------------
# trace_report --cache on a /metrics snapshot + --require-min-hit-rate
# ---------------------------------------------------------------------------
def test_trace_report_cache_from_metrics_snapshot(tmp_path, capsys):
    from tools.trace_report import (
        cache_metrics_summary,
        load_cache,
        main as report_main,
    )

    snap = "\n".join([
        "# HELP areal_tpu_gen_total_prompt_tokens x",
        "areal_tpu_gen_total_prompt_tokens 1000",
        "areal_tpu_gen_total_cached_prompt_tokens 400",
        "areal_tpu_gen_prefix_cache_hit_rate 0.4",
        "areal_tpu_gen_prefix_claim_hit_rate 0.5",
        "areal_tpu_gen_kv_tier_spilled_pages_total 12",
        "areal_tpu_gen_kv_tier_promoted_pages_total 9",
        "areal_tpu_gen_kv_tier_host_cached_tokens_total 144",
        "areal_tpu_gen_kv_tier_host_claim_hit_rate 0.25",
        "areal_tpu_gen_kv_tier_host_pages 3",
        "areal_tpu_gen_kv_ship_exports_total 2",
        "areal_tpu_gen_kv_ship_pages_in_total 6",
        "areal_tpu_gen_unrelated_gauge 7",  # filtered out
    ])
    path = tmp_path / "metrics.prom"
    path.write_text(snap + "\n")
    loaded = load_cache(str(path))
    ca = cache_metrics_summary(loaded["metrics"])
    assert ca["source"] == "metrics"
    assert ca["token_hit_rate"] == 0.4
    assert ca["tiers"]["host_cached_tokens"] == 144
    assert ca["tiers"]["device_cached_tokens"] == 400 - 144
    assert ca["tiers"]["spilled_pages"] == 12
    assert ca["ship"]["exports"] == 2 and ca["ship"]["pages_in"] == 6
    assert report_main([str(path), "--cache"]) == 0
    out = capsys.readouterr().out
    assert "host" in out.lower() and "ship" in out.lower()
    # the CI gate: passes at/below the measured rate, fails above it
    assert report_main(
        [str(path), "--cache", "--require-min-hit-rate", "0.3"]
    ) == 0
    assert report_main(
        [str(path), "--cache", "--require-min-hit-rate", "0.5"]
    ) == 1
    assert "below the gate" in capsys.readouterr().err


def test_trace_report_cache_metrics_without_tiers(tmp_path):
    # spill off → snapshot has no kv_tier_* keys → no tier section
    from tools.trace_report import cache_metrics_summary, load_cache

    path = tmp_path / "metrics.prom"
    path.write_text(
        "areal_tpu_gen_total_prompt_tokens 10\n"
        "areal_tpu_gen_prefix_cache_hit_rate 0.1\n"
    )
    ca = cache_metrics_summary(load_cache(str(path))["metrics"])
    assert ca["tiers"] is None and ca["ship"] is None


@pytest.mark.slow
def test_cross_server_ship_e2e(engine_factory):
    """Affinity-miss shipping end to end: two HTTP servers behind a
    router with --kv-ship; the session's affine server is retired, the
    replacement serves the next turn from shipped pages."""
    import json as _json
    import urllib.request

    from areal_tpu.inference.router import RouterState
    from areal_tpu.inference.server import serve
    from areal_tpu.api.cli_args import TrafficConfig

    a = engine_factory(prefix_reuse_min=16, kv_ship=True, admit_wave=1)
    b = engine_factory(prefix_reuse_min=16, kv_ship=True, admit_wave=1)
    sa = serve(a, host="127.0.0.1", port=0, background=True)
    sb = serve(b, host="127.0.0.1", port=0, background=True)
    try:
        addr_a = f"127.0.0.1:{sa.server_address[1]}"
        addr_b = f"127.0.0.1:{sb.server_address[1]}"
        router = RouterState(
            [addr_a, addr_b], schedule_policy="round_robin",
            traffic=TrafficConfig(kv_ship=True),
        )

        def gen(addr, tokens, ship_from=None):
            payload = {
                "input_ids": [int(t) for t in tokens],
                "sampling_params": {"max_new_tokens": 6, "greedy": True},
            }
            if ship_from:
                payload["kv_ship_from"] = ship_from
            req = urllib.request.Request(
                f"http://{addr}/generate",
                data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                return _json.loads(r.read())

        qid = "session-1"
        out1 = router._schedule({"rid": "r1", "qid": qid})
        first = out1["url"]
        assert "kv_ship_from" not in out1
        r1 = gen(first, np.random.default_rng(3).integers(1, 128, 48))
        # the affine server retires (drain/rebalance): the router evicts
        # its qids but remembers it as the shipping source
        router.evict_server(first)
        out2 = router._schedule({"rid": "r2", "qid": qid})
        second = out2["url"]
        assert second != first
        assert out2.get("kv_ship_from") == first
        # turn 2 = turn 1 prompt + output; the hint rides the payload
        turn2 = [int(t) for t in
                 np.random.default_rng(3).integers(1, 128, 48)]
        turn2 += r1["output_ids"]
        r2 = gen(second, turn2, ship_from=out2["kv_ship_from"])
        # served from shipped pages: cached, and no re-prefill of the
        # shipped prefix on the replacement server
        assert r2["meta_info"]["cached_tokens"] >= 32
        eng2 = a if second == addr_a else b
        eng1 = b if second == addr_a else a
        assert eng1.metrics()["kv_ship_exports_total"] >= 1
        assert eng2.metrics()["kv_ship_imports_total"] >= 1
        assert eng2.metrics()["kv_ship_pages_in_total"] >= 2
        # router surfaced the hint exactly once, and only with kv_ship
        assert router.kv_ship_hints_total == 1
        assert "kv_ship_hints_total" in router.metrics()
    finally:
        sa.shutdown()
        sb.shutdown()
