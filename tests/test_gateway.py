"""Serving control plane: multi-replica gateway drills
(paddle_tpu.inference.gateway).

The acceptance bars:
  * routing policies (least-loaded, session/bucket affinity, weighted
    round-robin) over a 2-replica pool produce TOKEN-EXACT outputs vs
    solo ``generate``;
  * per-tenant quotas and the two-level priority queue keep a
    low-priority tenant completing under saturating high-priority load;
  * a chaos-killed replica's in-flight requests requeue onto survivors
    (``gateway.requeued`` > 0) and finish with zero lost or duplicated
    tokens — streaming consumers see the failover transparently.

Everything is single-threaded and deterministic: the gateway's step()
IS the simulation harness (no multiprocessing).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.gateway import (DispatchQueue, Gateway,
                                          PRIORITY_LOW, TenantQuotas,
                                          TokenBucket)
from paddle_tpu.inference.serving import ContinuousBatcher
from paddle_tpu.resilience import (DeadlineExceeded, Overloaded,
                                   arm_scenario, disarm)

from greedy_ref import greedy_ref

pytestmark = pytest.mark.gateway


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
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, size=n).astype(np.int64) for n in sizes]


_ref = greedy_ref


def _batcher(lm, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("s_max", 64)
    return ContinuousBatcher(lm, compile=False, **kw)


# -- unit pieces --------------------------------------------------------------

def test_token_bucket_refills_on_injected_clock():
    t = [0.0]
    b = TokenBucket(rate=10.0, burst=20.0, clock=lambda: t[0])
    assert b.try_take(20)            # starts full
    assert not b.try_take(1)         # empty; nothing charged on refusal
    t[0] = 0.5                       # +5 tokens
    assert b.level == pytest.approx(5.0)
    assert b.try_take(5) and not b.try_take(0.1)
    t[0] = 100.0
    assert b.level == pytest.approx(20.0)   # capped at burst

    q = TenantQuotas({"metered": TokenBucket(1.0, 4.0, clock=lambda: t[0])})
    assert q.admit("unmetered", 10_000)     # no bucket -> unlimited
    assert q.admit("metered", 4) and not q.admit("metered", 1)


def test_dispatch_queue_low_share_prevents_starvation():
    class R:
        def __init__(self, tag, pr):
            self.tag, self.priority = tag, pr

    q = DispatchQueue(low_share=3)
    for i in range(6):
        q.push(R(f"h{i}", 0))
    q.push(R("low", PRIORITY_LOW))
    order = [q.pop().tag for _ in range(len(q))]
    # every 3rd dispatch serves the low lane: the batch request lands at
    # position 3, not dead last
    assert order == ["h0", "h1", "low", "h2", "h3", "h4", "h5"]


# -- token-exact routing ------------------------------------------------------

def test_gateway_least_loaded_token_exact_across_two_replicas(lm):
    prompts = _prompts(0, (5, 9, 7, 12))
    refs = [_ref(lm, p, 8) for p in prompts]
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    gids = [gw.submit(p, 8) for p in prompts]
    out = gw.run_until_done()
    for g, ref in zip(gids, refs):
        assert np.array_equal(out[g], ref)
    # 4 requests over 2x2 slots: least-loaded spreads — both engines served
    assert all(r.batcher.stats()["completed_requests"] == 2
               for r in gw.pool.replicas())
    assert gw.stats()["completions"] == 4


def test_gateway_session_affinity_sticks_and_stays_exact(lm):
    from paddle_tpu.observability.metrics import get_registry
    hits0 = get_registry().counter(
        "gateway.route.affinity_hit", "").value
    # two sessions in DIFFERENT prompt buckets (6 -> rung 8, 20 -> rung
    # 32), two turns each, a turn at a time so turn 2 has a sticky target
    prompts = _prompts(1, (6, 20, 6, 20))
    refs = [_ref(lm, p, 6) for p in prompts]
    gw = Gateway(policy="affinity")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    gids, serving = [], {}
    for i, p in enumerate(prompts):
        sid = f"s{i % 2}"
        gids.append(gw.submit(p, 6, session_id=sid))
        gw.step()
        serving.setdefault(sid, set()).add(
            gw.router._sessions[sid])
    out = gw.run_until_done()
    for g, ref in zip(gids, refs):
        assert np.array_equal(out[g], ref)
    # each session's turns all landed on ONE replica
    assert all(len(reps) == 1 for reps in serving.values())
    assert get_registry().counter(
        "gateway.route.affinity_hit", "").value > hits0

    # bucket warmth without a session: a same-rung prompt prefers the
    # replica that already compiled that prefill rung, even when it is
    # the busier one
    gw2 = Gateway(policy="affinity")
    gw2.add_replica("r0", _batcher(lm, max_batch=4))
    gw2.add_replica("r1", _batcher(lm, max_batch=4))
    gw2.submit(_prompts(2, (6,))[0], 6)
    gw2.step()                               # r0 warms rung 8, load 1
    gw2.submit(_prompts(3, (7,))[0], 6)      # rung 8 again
    gw2.step()
    assert gw2.pool.get("r0").load == 2      # warm beat least-loaded
    gw2.run_until_done()


def test_gateway_weighted_rr_respects_weights(lm):
    prompts = _prompts(2, (4, 4, 4, 4, 4, 4))
    refs = [_ref(lm, p, 4) for p in prompts]
    gw = Gateway(policy="weighted_rr")
    gw.add_replica("heavy", _batcher(lm, max_batch=8), weight=2.0)
    gw.add_replica("light", _batcher(lm, max_batch=8), weight=1.0)
    gids = [gw.submit(p, 4) for p in prompts]
    gw.step()                        # all 6 dispatch into 8+8 free slots
    loads = {r.name: r.load for r in gw.pool.replicas()}
    assert loads == {"heavy": 4, "light": 2}     # smooth 2:1 split
    out = gw.run_until_done()
    for g, ref in zip(gids, refs):
        assert np.array_equal(out[g], ref)


# -- quotas / priorities / SLO ------------------------------------------------

def test_gateway_tenant_quota_sheds_typed(lm):
    gw = Gateway(quotas=TenantQuotas(
        {"free": TokenBucket(rate=0.0, burst=20.0)}))
    gw.add_replica("r0", _batcher(lm))
    gw.submit(np.arange(4), 8, tenant="free")       # cost 12: fits
    with pytest.raises(Overloaded):
        gw.submit(np.arange(4), 8, tenant="free")   # bucket exhausted
    gw.submit(np.arange(4), 8, tenant="paid")       # unmetered tenant fine
    assert len(gw.run_until_done()) == 2


def test_gateway_low_priority_tenant_not_starved(lm):
    """Saturating high-priority load on a 1-slot replica: the low lane's
    guaranteed share still gets the batch request through EARLY, not
    after the entire high backlog."""
    gw = Gateway(low_share=2)
    gw.add_replica("r0", _batcher(lm, max_batch=1))
    high = [gw.submit(p, 4, tenant="interactive")
            for p in _prompts(3, (4, 4, 4, 4))]
    low = gw.submit(_prompts(4, (4,))[0], 4, tenant="batch",
                    priority="low")
    finish_order = []
    for _ in range(500):
        finish_order += gw.step()
        if not gw._has_work():
            break
    assert set(finish_order) == set(high) | {low}
    # low_share=2 -> the low request is the 2nd dispatch on the single
    # slot; it must beat at least the last three high requests
    assert finish_order.index(low) <= 1


def test_gateway_slo_admission_and_queue_expiry(lm):
    gw = Gateway(slo_tpot_s=10.0)            # absurd TPOT estimate
    gw.add_replica("r0", _batcher(lm))
    with pytest.raises(DeadlineExceeded):    # 10 tokens can't fit 0.5s
        gw.submit(np.arange(4), 10, deadline_s=0.5)
    assert gw.stats()["infeasible"] == 1

    gw2 = Gateway()                          # no replicas: work waits
    gid = gw2.submit(np.arange(4), 4, deadline_s=0.0)
    time.sleep(0.001)
    gw2.step()
    with pytest.raises(DeadlineExceeded):
        gw2.result(gid)
    st = gw2.stats()
    assert st["deadline_expired"] == 1 and st["shed"] == 0


def test_gateway_queue_capacity_sheds_typed(lm):
    gw = Gateway(max_queue_depth=1)
    gw.submit(np.arange(4), 4)
    with pytest.raises(Overloaded):
        gw.submit(np.arange(4), 4)
    assert gw.stats()["shed"] == 1


# -- lifecycle / failure drills ----------------------------------------------

def test_gateway_drain_routes_around_and_remove(lm):
    prompts = _prompts(5, (5, 7, 9))
    refs = [_ref(lm, p, 5) for p in prompts]
    gw = Gateway()
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    g0 = gw.submit(prompts[0], 5)
    gw.step()                                # lands on r0 (least loaded tie)
    gw.drain_replica("r0")
    g1, g2 = gw.submit(prompts[1], 5), gw.submit(prompts[2], 5)
    out = gw.run_until_done()
    for g, ref in zip((g0, g1, g2), refs):
        assert np.array_equal(out[g], ref)
    # drained replica finished its in-flight work but took nothing new
    assert gw.pool.get("r0").batcher.stats()["completed_requests"] == 1
    assert gw.pool.get("r1").batcher.stats()["completed_requests"] == 2
    gw.remove_replica("r0")                  # empty + drained: clean remove
    assert "r0" not in gw.pool


def test_gateway_replica_death_requeues_token_exact(lm):
    """THE failover drill: chaos kills one replica mid-decode (its step
    exhausts the pool's retry policy); every in-flight request resumes
    on the survivor and completes token-exact — zero lost or duplicated
    tokens, gateway.requeued > 0. A streaming consumer rides through the
    failover without noticing."""
    prompts = _prompts(6, (5, 9, 7, 11))
    refs = [_ref(lm, p, 10) for p in prompts]
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    gids = [gw.submit(p, 10) for p in prompts]
    sess = gw.open_stream(gids[0])
    # 3 consecutive serving.step faults == the pool retry budget -> the
    # replica holding them dies; deterministic seed + hit counting picks
    # a mid-flight moment (after=6 engine steps across the pool)
    arm_scenario("seed=0; serving.step:transient_error:after=6,count=3")
    streamed = list(sess)                    # consumer-paced: drives step()
    for _ in range(1000):
        if not gw._has_work():
            break
        gw.step()
    s = gw.stats()
    assert s["requeued"] > 0
    alive = [r for r in gw.pool.replicas() if r.alive]
    assert len(alive) == 1                   # exactly one casualty
    # the duplicated-work interval is tagged: the survivor's prompt
    # re-prefill carries requeue_recompute=1 (the interrupted spans mark
    # what was cut short; THIS marks what gets paid twice), and the
    # goodput ledger prices it as waste.requeue_recompute
    from paddle_tpu.observability import (build_waterfalls, get_recorder,
                                          ledger_from_waterfalls)
    tids = {gw._finished[g].trace.trace_id for g in gids
            if gw._finished[g].trace is not None}
    wfs = [w for w in build_waterfalls(get_recorder().spans())
           if w.trace_id in tids]
    recomputes = [seg for w in wfs for seg in w.segments
                  if seg.tags.get("requeue_recompute")]
    assert recomputes and all(seg.name == "prefill" for seg in recomputes)
    assert all(seg.tags.get("replica") == alive[0].name
               for seg in recomputes)        # charged to the survivor
    led = ledger_from_waterfalls(wfs)
    assert led.waste["requeue_recompute"] > 0.0
    for g, ref in zip(gids, refs):
        assert np.array_equal(gw.pop_result(g), ref)  # zero lost/dup tokens
    assert streamed == [int(t) for t in refs[0][len(prompts[0]):]]
    assert s["completions"] == 4 and s["failures"] == 0


def test_gateway_tp_shard_group_member_death_requeues_token_exact(lm):
    """Tensor-parallel flavor of the failover drill: replica r0 is a
    2-way TP shard group (weights P(None,'tensor'), KV sharded on
    heads). Chaos kills ONE group member mid-decode; the batcher's
    heartbeat raises the non-retryable TPMemberDied, the pool declares
    the WHOLE group dead (a member held 1/2 of the weights), and every
    in-flight request resumes token-exact on the plain survivor."""
    from paddle_tpu.distributed.mesh import MeshRuntime
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM

    # a private model instance: shard_serving re-places its weights on
    # the mesh, which must not leak into the module-scoped fixture
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    tp_lm = GPT2ForCausalLM(cfg)
    tp_lm.eval()

    prompts = _prompts(6, (5, 9, 7, 11))
    refs = [_ref(lm, p, 10) for p in prompts]
    gw = Gateway(policy="least_loaded")
    b0 = _batcher(tp_lm)
    group = MeshRuntime({"tensor": 2}).shard_serving(b0, group_name="tp0")
    gw.add_replica("r0", b0)
    gw.add_replica("r1", _batcher(lm))
    rep0 = gw.pool.get("r0")
    assert rep0.shard_group is group and "tp=tp0x2" in repr(rep0)

    gids = [gw.submit(p, 10) for p in prompts]
    arm_scenario("seed=0; serving.tp_member:transient_error:after=6,count=1")
    for _ in range(1000):
        if not gw._has_work():
            break
        gw.step()
    s = gw.stats()
    assert s["requeued"] > 0
    assert [r.name for r in gw.pool.replicas() if not r.alive] == ["r0"]
    assert group.failed_members == ["tp0/tensor1"]
    assert rep0.describe()["shard_group"]["failed"] == ["tp0/tensor1"]
    for g, ref in zip(gids, refs):
        assert np.array_equal(gw.pop_result(g), ref)  # zero lost/dup tokens
    assert s["completions"] == 4 and s["failures"] == 0


def test_affinity_policy_prefers_deepest_cached_prefix():
    """KV-aware tier: the replica advertising the deepest chain-hash
    match wins over session/bucket warmth and load order."""
    from paddle_tpu.inference.gateway import SessionAffinityPolicy
    from paddle_tpu.inference.prefix_cache import RadixPrefixCache
    from paddle_tpu.observability.metrics import get_registry

    class FakeRep:
        def __init__(self, name, cache, load=0):
            self.name, self._cache, self.load = name, cache, load
            self.warm_buckets = set()

        def prefix_summary(self):
            return None if self._cache is None else self._cache.summary()

    deep = RadixPrefixCache(4)
    deep.insert(np.arange(8), [0, 1], 0, 2)         # 2 cached blocks
    shallow = RadixPrefixCache(4)
    shallow.insert(np.arange(4), [0], 0, 1)         # 1 cached block
    reps = [FakeRep("a", shallow), FakeRep("b", deep, load=5),
            FakeRep("c", None)]
    pol = SessionAffinityPolicy()

    class Req:
        prompt = np.arange(12)
        session_id = "sticky"
        bucket = None
    pol._sessions["sticky"] = "a"                   # stickiness says a…
    px = get_registry().counter("gateway.route.prefix_hit", "t")
    before = px.value
    assert pol.select(Req(), reps).name == "b"      # …prefix depth wins
    assert px.value - before == 1
    # no cached prefix anywhere -> the classic tiers take over (session)
    class Cold:
        prompt = np.arange(100, 112)
        session_id = "sticky"
        bucket = None
    assert pol.select(Cold(), reps).name == "a"


def test_gateway_failover_with_speculation_reprefixes(lm):
    """Round-13 drill: paged replicas with the radix prefix cache AND a
    draft model attached; chaos kills one mid-decode. The lost/dup-token
    guard must hold (token-exact results), the requeued requests must
    re-match their cached prefix on the survivor, and no page may leak."""
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    from paddle_tpu.models.gpt import GPT2ForCausalLM
    from paddle_tpu.observability.metrics import get_registry

    paddle.seed(42)
    draft = GPT2ForCausalLM(lm.config)              # disagreeing draft
    draft.eval()
    rng = np.random.RandomState(21)
    shared = rng.randint(0, 128, (16,)).astype(np.int64)   # 2 blocks of 8
    prompts = [np.concatenate([shared, t])
               for t in _prompts(22, (5, 7, 6, 9))]
    refs = [_ref(lm, p, 16) for p in prompts]

    def paged(seed_tag):
        return PagedContinuousBatcher(
            lm, max_batch=2, s_max=64, block_size=8, n_pages=32,
            compile=False, prefix_cache=True, draft_model=draft,
            draft_k=3)

    gw = Gateway(policy="affinity")
    gw.add_replica("r0", paged("r0"))
    gw.add_replica("r1", paged("r1"))
    gids = [gw.submit(p, 16) for p in prompts]
    arm_scenario("seed=0; serving.step:transient_error:after=6,count=3")
    dead = None
    for _ in range(2000):
        gw.step()
        dead = next((r for r in gw.pool.replicas() if not r.alive), None)
        if dead is not None:
            break
    assert dead is not None, "chaos never killed a replica"
    survivor = next(r for r in gw.pool.replicas() if r.alive)
    hits_before = survivor.batcher.prefix_cache.hit_tokens
    for _ in range(2000):
        if not gw._has_work():
            break
        gw.step()
    s = gw.stats()
    assert s["requeued"] > 0 and s["failures"] == 0
    # zero lost/duplicated tokens: exact output through spec + failover
    # (the gateway's accounting guard would have raised on divergence)
    for g, ref in zip(gids, refs):
        assert np.array_equal(gw.pop_result(g), ref)
    # requeued requests re-matched the shared prefix on the survivor
    assert survivor.batcher.prefix_cache.hit_tokens > hits_before
    assert survivor.batcher.spec_stats["rounds"] > 0
    survivor.batcher.audit_pages()
    assert get_registry().gauge("serving.pages_leaked", "t").value == 0


# -- streaming ----------------------------------------------------------------

def test_gateway_streaming_delivery_and_backpressure(lm):
    prompt = _prompts(7, (6,))[0]
    ref = _ref(lm, prompt, 8)
    gw = Gateway()
    gw.add_replica("r0", _batcher(lm, max_batch=4))
    sess = gw.stream(prompt, 8, max_buffered=2)
    while not sess.throttled:                # decode until buffer fills
        gw.step()
    late = gw.submit(_prompts(8, (4,))[0], 4)
    gw.step()
    # full buffer pauses INTAKE: the late request stays in the gateway
    # queue while the throttle holds
    assert gw.stats()["queue_depth"] == 1
    got = sess.read_available()              # consumer catches up
    gw.step()
    assert gw.stats()["queue_depth"] == 0    # late request dispatched
    got += list(sess)
    assert got == [int(t) for t in ref[len(prompt):]]
    gw.run_until_done()                      # flush whatever remains
    assert len(gw.pop_result(late)) == 8     # 4 prompt + 4 generated
    assert np.array_equal(gw.pop_result(sess.gid), ref)
    with pytest.raises(KeyError):
        gw.open_stream(sess.gid)             # finished: no longer live
