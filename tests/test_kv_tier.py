"""Tiered radix KV cache (round 17): host-DRAM spill + async promotion.

Four layers, <60s total:

  * tier units — HostTier/DiskTier byte accounting, blob roundtrips,
    demotion state machine (device -> host -> disk -> gone), host-LRU
    overflow, the cached-summary invalidation contract, and the
    ``evictable_pages() == evict(n)`` property under interleaved
    pin/unpin (no model, sub-second);
  * transfer plumbing — AsyncLoader futures + idempotent bounded close,
    DevicePrefetcher.close() waking a feeder blocked mid-put;
  * serving integration — churn workloads (working set > device pool)
    must stay TOKEN-EXACT vs solo ``generate`` across seeds with
    demotions and promotions actually happening, pages + tier bytes
    audited to zero leak; chaos at ``kv.host_demote``/``kv.host_promote``
    must degrade to recompute/full prefill, still token-exact;
  * control plane — the router prefers device-resident prefix depth,
    the gateway failover drill stays token-exact with tiered replicas,
    and ``telemetry_dump --prefix-stats`` reports the per-tier columns.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.prefix_cache import (DiskTier, HostTier,
                                               RadixPrefixCache,
                                               blob_nbytes, chain_hashes)
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.resilience import arm_scenario, disarm

from greedy_ref import greedy_ref

pytestmark = pytest.mark.kvtier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm():
    disarm()
    yield
    disarm()


@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


_ref = greedy_ref


def _churn_prompts(seed, n_prefixes=6, n_requests=14, prefix_len=48,
                   tail=5):
    """Churn stream: one cold pass over every shared prefix (the working
    set — n_prefixes * 3 pages at block 16 — overflows the device pool,
    so the early chains demote), then random re-references that must
    come back via promotion. Tails are unique per request."""
    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(0, 128, (prefix_len,)).astype(np.int64)
                for _ in range(n_prefixes)]
    picks = (list(range(n_prefixes))
             + list(rng.randint(0, n_prefixes,
                                (max(n_requests - n_prefixes, 0),))))
    return [np.concatenate([prefixes[p], rng.randint(0, 128, (tail,))])
            for p in picks]


def _tiered(lm, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("s_max", 96)
    kw.setdefault("block_size", 16)
    kw.setdefault("n_pages", 14)
    kw.setdefault("compile", False)
    kw.setdefault("policy", "ondemand")
    kw.setdefault("prefix_cache", True)
    kw.setdefault("host_kv_gib", 0.25)
    return PagedContinuousBatcher(lm, **kw)


# -- tier units (no model) ----------------------------------------------------

def _blob(fill, shape=(2, 4)):
    return {"t": [(np.full(shape, fill, np.float32),
                   np.full(shape, fill + 1, np.float32))]}


def test_host_tier_accounting():
    t = HostTier(capacity_bytes=1 << 20)
    b = _blob(1.0)
    nb = t.put(7, b)
    assert nb == blob_nbytes(b) == t.used_bytes
    assert 7 in t and len(t) == 1 and t.stored == 1
    assert t.get(7) is b and t.nbytes_of(7) == nb
    assert t.discard(7) == nb
    assert t.used_bytes == 0 and 7 not in t


def test_disk_tier_roundtrip_and_unlink(tmp_path):
    t = DiskTier(str(tmp_path / "kv"), capacity_bytes=1 << 20)
    blob = {"t": [(np.arange(8, dtype=np.float32).reshape(2, 4),
                   np.ones((2, 4), np.float16))],
            "d": [(np.zeros((1, 2), np.float32),) * 2]}
    t.put(3, blob)
    back = t.get(3)
    assert back["t"][0][0].dtype == np.float32
    assert back["t"][0][1].dtype == np.float16
    np.testing.assert_array_equal(back["t"][0][0], blob["t"][0][0])
    np.testing.assert_array_equal(back["d"][0][1], blob["d"][0][1])
    files = os.listdir(str(tmp_path / "kv"))
    assert len(files) == 1
    t.discard(3)
    assert os.listdir(str(tmp_path / "kv")) == [] and t.used_bytes == 0


def _tiered_cache(block_size=4, host_cap=1 << 20, disk=None):
    tier = HostTier(host_cap, next_tier=disk)
    c = RadixPrefixCache(block_size, host_tier=tier,
                         spill=lambda node: _blob(float(node.page)))
    return c, tier


def test_demote_keeps_chain_matchable_and_splits():
    c, tier = _tiered_cache()
    toks = np.arange(12)                                   # 3 blocks
    created = c.insert(toks, pages=[5, 6, 7], start_block=0, n_blocks=3)
    c.unpin(created)
    freed = c.evict(2)                                     # deepest first
    assert freed == [7, 6]
    assert c.cached_pages == 1 and len(c) == 3             # nodes survive
    path = c.match(toks)
    assert len(path) == 3
    dev, hosted = RadixPrefixCache.split_device(path)
    assert [n.page for n in dev] == [5]
    assert [n.residency for n in hosted] == ["host", "host"]
    assert all(n.page == -1 for n in hosted)
    rep = c.audit_tiers()
    assert rep["host_nodes"] == 2 and rep["host_bytes"] == tier.used_bytes
    # promotion flips them back and drops the blobs
    c.promote_node(hosted[0], page=8, nbytes=64)
    c.promote_node(hosted[1], page=9, nbytes=64)
    assert c.cached_pages == 3 and c.audit_tiers()["host_nodes"] == 0
    assert c.promotions == 2 and c.promoted_bytes == 128


def test_demote_failure_drops_subtree_cleanly():
    tier = HostTier(1 << 20)
    calls = {"n": 0}

    def spill(node):
        calls["n"] += 1
        raise RuntimeError("pool read failed")

    c = RadixPrefixCache(4, host_tier=tier, spill=spill)
    created = c.insert(np.arange(8), [1, 2], 0, 2)
    c.unpin(created)
    freed = c.evict(2)
    assert freed == [2, 1] and calls["n"] == 2
    assert len(c) == 0 and c.cached_pages == 0
    assert c.demote_failures == 2 and c.demotions == 0
    assert c.audit_tiers() == {"host_bytes": 0, "host_nodes": 0}


def test_host_lru_overflow_spills_to_disk(tmp_path):
    one = blob_nbytes(_blob(0.0))
    disk = DiskTier(str(tmp_path / "kv"), capacity_bytes=1 << 20)
    c, tier = _tiered_cache(host_cap=2 * one, disk=disk)
    # three independent single-block chains demoted through a 2-blob host
    chains = [np.array([i, i, i, i]) for i in range(3)]
    for i, toks in enumerate(chains):
        created = c.insert(toks, [10 + i], 0, 1)
        c.unpin(created)
        c.evict(1)
    assert c.demotions == 3
    rep = c.audit_tiers()
    assert rep["host_nodes"] == 2 and rep["disk_nodes"] == 1
    assert tier.evicted == 1                  # host LRU pushed down-chain
    # the disk-resident node (first demoted = LRU victim) still matches
    # and its blob reads back through the same interface
    path = c.match(chains[0])
    assert len(path) == 1 and path[0].residency == "disk"
    assert blob_nbytes(c.node_blob(path[0])) == one


def test_host_overflow_without_disk_drops():
    one = blob_nbytes(_blob(0.0))
    c, tier = _tiered_cache(host_cap=one)     # room for exactly one blob
    for i in range(2):
        created = c.insert(np.array([i] * 4), [20 + i], 0, 1)
        c.unpin(created)
        c.evict(1)
    assert len(c) == 1 and tier.evicted == 1  # first chain is gone
    assert c.match(np.array([0] * 4)) == []
    assert c.match(np.array([1] * 4))[0].residency == "host"
    c.audit_tiers()


def test_summary_cached_and_invalidated_on_every_transition():
    c, _ = _tiered_cache()
    created = c.insert(np.arange(8), [1, 2], 0, 2)
    s1 = c.summary()
    assert c.summary() is s1                   # cached between mutations
    h1, h2 = chain_hashes(np.arange(8), 4)
    assert s1["tiers"] == {h1: "device", h2: "device"}
    c.unpin(created)
    c.evict(1)                                 # demotion invalidates
    s2 = c.summary()
    assert s2 is not s1 and s2["tiers"][h2] == "host"
    node = c.match(np.arange(8))[1]
    c.promote_node(node, page=3)               # promotion invalidates
    s3 = c.summary()
    assert s3 is not s2 and s3["tiers"][h2] == "device"
    # untiered eviction (drop) removes the hash entirely
    u = RadixPrefixCache(4)
    cr = u.insert(np.arange(8), [1, 2], 0, 2)
    u.unpin(cr)
    s4 = u.summary()
    u.evict(2)
    s5 = u.summary()
    assert s5 is not s4 and s5["hashes"] == {}


def test_evictable_pages_equals_evict_under_pin_churn():
    """Satellite property: the capacity planner (evictable_pages) and
    the executor (evict) agree EXACTLY at every point of an interleaved
    insert/pin/unpin/evict history — tiered and untiered."""
    for tiered in (False, True):
        if tiered:
            c, _ = _tiered_cache(block_size=2, host_cap=1 << 20)
        else:
            c = RadixPrefixCache(2)
        rng = np.random.RandomState(7 + tiered)
        next_page = [0]
        pinned = []                            # (nodes) we must release

        def fresh_pages(n):
            out = list(range(next_page[0], next_page[0] + n))
            next_page[0] += n
            return out

        for step in range(60):
            op = rng.randint(4)
            if op == 0:                        # insert a random chain
                blocks = rng.randint(1, 4)
                toks = rng.randint(0, 4, (blocks * 2,))
                created = c.insert(toks, fresh_pages(blocks), 0, blocks)
                if created and rng.randint(2):
                    c.unpin(created)
                elif created:
                    pinned.append(created)
            elif op == 1 and pinned:           # release an old pin
                c.unpin(pinned.pop(rng.randint(len(pinned))))
            elif op == 2:                      # pin a matched path
                toks = rng.randint(0, 4, (rng.randint(1, 4) * 2,))
                path = c.match(toks)
                if path:
                    c.pin(path)
                    pinned.append(path)
            else:                              # the property checkpoint
                want = c.evictable_pages()
                freed = c.evict(want + 7)      # ask for MORE than exists
                assert len(freed) == want, (tiered, step)
        for nodes in pinned:
            c.unpin(nodes)
        assert c.evictable_pages() == len(c.evict(10 ** 6))
        assert c.cached_pages == 0


# -- transfer plumbing --------------------------------------------------------

def test_async_loader_future_and_idempotent_close():
    from paddle_tpu.perf.prefetch import AsyncLoader
    ld = AsyncLoader(depth=2)
    payload = [np.arange(6, dtype=np.float32), np.ones((2, 2))]
    fut = ld.submit(payload)
    out = fut.result(timeout=10.0)
    assert fut.done()
    np.testing.assert_array_equal(np.asarray(out[0]), payload[0])
    ld.close()
    ld.close()                                 # second close is a no-op
    assert not any(t.is_alive() for t in ld._threads)
    with pytest.raises(RuntimeError):
        ld.submit(payload)


def test_device_prefetcher_close_wakes_blocked_feeder():
    from paddle_tpu.perf.prefetch import DevicePrefetcher

    def endless():
        i = 0
        while True:
            yield np.full((2,), i, np.float32)
            i += 1

    p = DevicePrefetcher(endless(), depth=1, transfer=lambda b: b)
    first = next(p)                            # feeder now blocks on put
    assert first is not None
    p.close(timeout=5.0)
    assert p._retired and not p._thread.is_alive()
    p.close(timeout=5.0)                       # idempotent
    with pytest.raises(StopIteration):
        next(p)


# -- serving integration ------------------------------------------------------

def test_tiered_churn_token_exact_across_seeds(lm):
    """Working set (6 prefixes x 3 blocks = 18 pages) over a 14-page
    pool: demotion + promotion must both fire and every output must
    equal solo generate. Zero leaked pages, zero leaked host bytes."""
    for seed in (3, 11):
        prompts = _churn_prompts(seed)
        refs = [_ref(lm, p, 4) for p in prompts]
        bt = _tiered(lm)
        try:
            rids = [bt.submit(p, 4) for p in prompts]
            outs = bt.run_until_done(max_steps=20000)
            for rid, ref in zip(rids, refs):
                np.testing.assert_array_equal(outs[rid], ref)
            st = bt.prefix_cache.stats()
            assert st["demotions"] > 0, seed
            assert st["promotions"] > 0, seed
            assert st["host_hit_tokens"] > 0, seed
            bt.audit_pages()                   # device cover + tier bytes
            assert bt._promo is None
        finally:
            bt.close()


def test_promotion_chaos_degrades_to_full_prefill(lm):
    """kv.host_promote fault on EVERY attempt: admission must fall back
    to full prefill (token-exact), count the failures, promote nothing,
    and leave pages + tiers clean."""
    prompts = _churn_prompts(5, n_requests=10)
    refs = [_ref(lm, p, 4) for p in prompts]
    bt = _tiered(lm)
    try:
        arm_scenario("seed=0; kv.host_promote:transient_error:count=999")
        rids = [bt.submit(p, 4) for p in prompts]
        outs = bt.run_until_done(max_steps=20000)
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(outs[rid], ref)
        st = bt.prefix_cache.stats()
        assert st["promotion_failures"] > 0
        assert st["promotions"] == 0
        assert st["demotions"] > 0             # spill itself kept working
        bt.audit_pages()
    finally:
        bt.close()


def test_demotion_chaos_drops_chains_cleanly(lm):
    """kv.host_demote faults on half the spills: failed demotions drop
    the chain (recompute next time) instead of leaking pages or bytes;
    outputs stay token-exact."""
    prompts = _churn_prompts(9, n_requests=10)
    refs = [_ref(lm, p, 4) for p in prompts]
    bt = _tiered(lm)
    try:
        arm_scenario("seed=0; kv.host_demote:transient_error:p=0.5")
        rids = [bt.submit(p, 4) for p in prompts]
        outs = bt.run_until_done(max_steps=20000)
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(outs[rid], ref)
        st = bt.prefix_cache.stats()
        assert st["demote_failures"] > 0
        bt.audit_pages()
    finally:
        bt.close()


def test_promotion_latency_histogram_populates(lm):
    from paddle_tpu.observability.metrics import get_registry
    h = get_registry().histogram("serving.prefix_promotion_seconds")
    before = h.count
    prompts = _churn_prompts(13, n_requests=10)
    bt = _tiered(lm)
    try:
        for p in prompts:
            bt.submit(p, 4)
        bt.run_until_done(max_steps=20000)
        assert h.count > before
        assert h.quantile(0.99) is not None
    finally:
        bt.close()


# -- control plane ------------------------------------------------------------

class _FakeReplica:
    def __init__(self, name, summary, load=0):
        self.name = name
        self.load = load
        self.weight = 1.0
        self.warm_buckets = set()
        self._summary = summary

    def prefix_summary(self):
        return self._summary


class _FakeReq:
    session_id = None
    bucket = None

    def __init__(self, prompt):
        self.prompt = prompt


def test_router_prefers_device_resident_depth():
    from paddle_tpu.inference.gateway.router import SessionAffinityPolicy
    prompt = np.arange(8)
    h1, h2 = chain_hashes(prompt, 4)
    hashes = {h1: 1, h2: 2}
    all_dev = _FakeReplica("dev", {
        "block_size": 4, "hashes": hashes,
        "tiers": {h1: "device", h2: "device"}}, load=5)
    tail_host = _FakeReplica("hosty", {
        "block_size": 4, "hashes": hashes,
        "tiers": {h1: "device", h2: "host"}}, load=0)
    pol = SessionAffinityPolicy()
    # equal total depth: device-resident depth wins even at higher load
    assert pol.select(_FakeReq(prompt),
                      [tail_host, all_dev]) is all_dev
    # but total depth still dominates: a full host chain beats a
    # shallower device chain (promotion is a memcpy, prefill is flops)
    shallow_dev = _FakeReplica("shallow", {
        "block_size": 4, "hashes": {h1: 1}, "tiers": {h1: "device"}})
    full_host = _FakeReplica("deep", {
        "block_size": 4, "hashes": hashes,
        "tiers": {h1: "host", h2: "host"}})
    assert pol.select(_FakeReq(prompt),
                      [shallow_dev, full_host]) is full_host
    # pre-tier summaries (no "tiers" key) count as all-device
    legacy = _FakeReplica("legacy", {"block_size": 4, "hashes": hashes})
    assert pol.select(_FakeReq(prompt),
                      [tail_host, legacy]) is legacy


def test_gateway_failover_with_tiered_replicas_token_exact(lm):
    """The round-13 failover drill with host tiers armed: a chaos-killed
    tiered replica's requests requeue and finish token-exact; the
    survivor's pages AND tier bytes audit clean."""
    from paddle_tpu.inference.gateway import Gateway
    rng = np.random.RandomState(21)
    shared = rng.randint(0, 128, (32,)).astype(np.int64)
    prompts = [np.concatenate(
        [shared, rng.randint(0, 128, (n,)).astype(np.int64)])
        for n in (5, 7, 6, 9)]
    refs = [_ref(lm, p, 8) for p in prompts]
    gw = Gateway(policy="affinity")
    gw.add_replica("r0", _tiered(lm, n_pages=16))
    gw.add_replica("r1", _tiered(lm, n_pages=16))
    gids = [gw.submit(p, 8) for p in prompts]
    arm_scenario("seed=0; serving.step:transient_error:after=6,count=3")
    dead = None
    for _ in range(2000):
        gw.step()
        dead = next((r for r in gw.pool.replicas() if not r.alive), None)
        if dead is not None:
            break
    assert dead is not None, "chaos never killed a replica"
    for _ in range(4000):
        if not gw._has_work():
            break
        gw.step()
    s = gw.stats()
    assert s["requeued"] > 0 and s["failures"] == 0
    for g, ref in zip(gids, refs):
        np.testing.assert_array_equal(gw.pop_result(g), ref)
    for r in gw.pool.replicas():
        if r.alive:
            r.batcher.audit_pages()
            r.batcher.close()


def test_telemetry_dump_prefix_stats_reports_tier_columns(
        tmp_path, monkeypatch, capsys):
    from paddle_tpu.observability import fleet
    from paddle_tpu.observability.metrics import get_registry
    reg = get_registry()
    reg.counter("serving.prefix_hit_tokens", "t").inc(80)
    reg.counter("serving.prefix_miss_tokens", "t").inc(20)
    tier_c = reg.counter("serving.prefix_tier_hit_tokens", "t",
                         labelnames=("tier",))
    tier_c.labels(tier="device").inc(48)
    tier_c.labels(tier="host").inc(32)
    reg.counter("serving.prefix_promotions", "t").inc(2)
    reg.counter("serving.prefix_demoted_bytes", "t").inc(4096)
    reg.histogram("serving.prefix_promotion_seconds", "t").observe(0.02)
    monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
    fleet.reset_spool()
    try:
        fleet.spool_metrics()
    finally:
        fleet.reset_spool()
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "telemetry_dump", os.path.join(REPO, "tools",
                                       "telemetry_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--fleet", str(tmp_path), "--prefix-stats"])
    assert rc == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("# fleet prefix-stats "))
    stats = json.loads(line[len("# fleet prefix-stats "):])
    # >= because the process-global registry may carry traffic from the
    # serving tests above — the columns just have to be present and sane
    assert stats["hit_tokens_by_tier"]["host"] >= 32
    assert stats["hit_tokens_by_tier"]["device"] >= 48
    assert stats["promotions"] >= 2
    assert stats["demoted_bytes"] >= 4096
    assert stats["promotion_latency_p50_ms"] is not None
    assert stats["promotion_latency_p99_ms"] is not None
