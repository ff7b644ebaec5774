"""Durable sessions (round 20): crash-safe manifests + pipelined resume.

Five layers, <60s total:

  * manifest durability — publish/load roundtrip, the atomic
    temp+``os.replace`` pattern under a chaos torn write at
    ``kv.session_publish`` (typed ``publish_torn``/``torn_manifest``
    findings, the previous manifest stays sound), whole-document and
    per-entry CRC rejection, chain-hash drift, model-identity mismatch,
    and the ``kv.session_resume`` chaos seam degrading to None;
  * pin-through-demotion — a paused session's chain cascades host→disk
    under churn but never OUT of the last tier (``session_pin_drops``
    stays 0) while an unpinned control chain of the same shape drops;
    resume rides tiered promotion and stays bitwise token-exact against
    the uninterrupted two-turn reference, serial == pipelined;
  * transfer plumbing — ``AsyncLoader.close()`` fails QUEUED transfers
    with ``TransferCancelled`` deterministically while the in-flight
    one is allowed to land;
  * fleet drills — pause → kill the pinned replica → rescale → resume
    on a survivor (manifest-resolved, bitwise exact, pages audited), the
    mid-promotion replica kill finished by the survivor, drain/requeue
    preserving session pins, and a second gateway process resolving the
    session from the shared store alone;
  * tooling — the agentic traffic population (seed-deterministic,
    resumes audited by ``drive``), ``telemetry_dump --sessions``,
    ``tools/session_inspect.py`` verdicts, and the ``session:``
    bench_guard lane gating a synthetic goodput regression.
"""
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.inference.session_store import (SessionManifest,
                                                SessionStore,
                                                model_identity)
from paddle_tpu.resilience import arm_scenario, disarm

from greedy_ref import greedy_ref

pytestmark = pytest.mark.session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK_BYTES = 2 * 2 * 16 * 64 * 4      # layers x k/v x block x hidden x f32


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


def _tiered(lm, tmp, host_blocks=2, disk_blocks=64, slots=3, chunk=2,
            **kw):
    """Tiered batcher with a shared-store mount under ``tmp``: host tier
    sized in BLOCKS (so tests control exactly how far churn cascades),
    disk tier + manifest store on the shared volume."""
    kw.setdefault("max_batch", 2)
    kw.setdefault("s_max", 96)
    kw.setdefault("block_size", 16)
    kw.setdefault("n_pages", 14)
    kw.setdefault("compile", False)
    kw.setdefault("policy", "ondemand")
    kw.setdefault("prefix_cache", True)
    kw.setdefault("host_kv_gib", host_blocks * BLOCK_BYTES * 1.05 / 2**30)
    kw.setdefault("disk_kv_dir", os.path.join(str(tmp), "kv_disk"))
    kw.setdefault("disk_kv_gib", disk_blocks * BLOCK_BYTES * 1.05 / 2**30)
    kw.setdefault("session_store", os.path.join(str(tmp), "sessions"))
    kw.setdefault("promo_slots", slots)
    kw.setdefault("promo_chunk_blocks", chunk)
    return PagedContinuousBatcher(lm, **kw)


def _run(bt, prompt, n):
    rid = bt.submit(np.asarray(prompt, np.int64), n)
    return bt.run_until_done(max_steps=60000)[rid]


def _churn(bt, seed=3, n=10, length=51):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        bt.submit(rng.randint(0, 128, (length,)).astype(np.int64), 4)
    bt.run_until_done(max_steps=60000)


# -- manifest durability ------------------------------------------------------

def test_manifest_roundtrip_sessions_and_delete(tmp_path):
    from paddle_tpu.inference.prefix_cache import chain_hashes
    store = SessionStore(str(tmp_path))
    toks = list(range(40))
    m = SessionManifest(session_id="alpha/1 weird", token_ids=toks,
                        block_size=16, model="GPT2:deadbeef")
    assert m.chain == chain_hashes(toks, 16) and m.n_blocks == 2
    assert store.publish(m)
    assert store.sessions() == ["alpha/1 weird"]
    got = store.load("alpha/1 weird", expect_model="GPT2:deadbeef")
    assert got is not None
    assert got.token_ids == toks and got.chain == m.chain
    assert got.covered_tokens == 32
    assert store.findings == []
    assert store.delete("alpha/1 weird")
    assert store.load("alpha/1 weird") is None
    assert store.findings[-1].kind == "missing"


def test_publish_torn_write_typed_finding_and_heal(tmp_path):
    store = SessionStore(str(tmp_path))
    m = SessionManifest(session_id="s", token_ids=list(range(32)),
                        block_size=16)
    arm_scenario("seed=0; kv.session_publish:torn_write:offset=25,count=1")
    assert store.publish(m) is False
    assert store.findings[-1].kind == "publish_torn"
    # crash debris: only a .tmp exists — no reader trusts it
    assert os.path.exists(store.path_for("s") + ".tmp")
    assert store.load("s") is None
    assert store.findings[-1].kind == "torn_manifest"
    # the seam heals once chaos passes; the next publish is atomic
    assert store.publish(m) is True
    assert store.load("s").token_ids == list(range(32))


def test_torn_publish_never_clobbers_previous_manifest(tmp_path):
    store = SessionStore(str(tmp_path))
    v1 = SessionManifest(session_id="s", token_ids=list(range(32)),
                         block_size=16)
    assert store.publish(v1)
    arm_scenario("seed=0; kv.session_publish:torn_write:offset=9,count=1")
    v2 = SessionManifest(session_id="s", token_ids=list(range(48)),
                         block_size=16)
    assert store.publish(v2) is False
    disarm()
    got = store.load("s")            # previous manifest is still sound
    assert got is not None and got.token_ids == list(range(32))


def test_load_rejects_corruption_and_model_mismatch(tmp_path):
    import zlib
    store = SessionStore(str(tmp_path))
    m = SessionManifest(session_id="s", token_ids=list(range(48)),
                        block_size=16, model="GPT2:cafe0000")
    assert store.publish(m)
    fpath = store.path_for("s")
    sound = open(fpath, "rb").read()

    # 1. flip a token, keep the recorded CRCs -> document checksum
    doc = json.loads(sound)
    doc["tokens"][5] ^= 1
    open(fpath, "wb").write(json.dumps(doc, sort_keys=True).encode())
    assert store.load("s") is None
    assert store.findings[-1].kind == "checksum_mismatch"

    # 2. re-seal the document CRC over the drifted chain entry -> the
    # per-entry layer catches what the document layer now misses
    doc = json.loads(sound)
    doc["blocks"][1]["h"] = "0" * 16
    body = {k: v for k, v in doc.items() if k != "crc"}
    doc["crc"] = zlib.crc32(
        json.dumps(body, sort_keys=True).encode()) & 0xFFFFFFFF
    open(fpath, "wb").write(json.dumps(doc, sort_keys=True).encode())
    assert store.load("s") is None
    assert store.findings[-1].kind == "hash_drift"

    # 3. sound bytes, wrong serving model -> typed mismatch, no resume
    open(fpath, "wb").write(sound)
    assert store.load("s", expect_model="GPT2:00000001") is None
    assert store.findings[-1].kind == "model_mismatch"
    assert store.load("s", expect_model="GPT2:cafe0000") is not None


def test_resume_fault_chaos_seam_degrades_to_none(tmp_path):
    store = SessionStore(str(tmp_path))
    m = SessionManifest(session_id="s", token_ids=list(range(32)),
                        block_size=16)
    assert store.publish(m)
    arm_scenario("seed=0; kv.session_resume:transient_error:count=1")
    assert store.load("s") is None
    assert store.findings[-1].kind == "resume_fault"
    assert store.load("s") is not None       # fault exhausted


# -- pin-through-demotion + pipelined resume ---------------------------------

def test_session_pin_survives_churn_resume_rides_promotion(lm, tmp_path):
    """The tentpole property: churn cascades a paused session's chain
    down the tiers but never out; the resume promotes it back and the
    two-turn conversation is bitwise identical to never pausing."""
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    control = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    base1 = _ref(lm, prompt, 6)
    base2 = _ref(lm, np.concatenate([base1, cont]), 6)

    bt = _tiered(lm, tmp_path, host_blocks=2, disk_blocks=6)
    try:
        with paddle.no_grad():
            out1 = _run(bt, prompt, 6)
            np.testing.assert_array_equal(out1, base1)
            _run(bt, control, 6)             # same shape, NOT pinned
            assert bt.pause_session("conv", out1) is True
            _churn(bt)
            pins = bt._session_pins["conv"]
            assert len(pins) == 3
            res = {n.residency for n in pins}
            assert "gone" not in res and res != {"device"}, res
            st = bt.prefix_cache.stats()
            assert st["session_pin_drops"] == 0
            # the unpinned control chain was dropped by the same churn
            assert len(bt.prefix_cache.match(control)) < 3

            toks = bt.resume_session("conv")
            np.testing.assert_array_equal(toks, out1)
            out2 = _run(bt, np.concatenate([toks, cont]), 6)
            np.testing.assert_array_equal(out2, base2)
            assert bt.prefix_cache.stats()["promotions"] > 0
            bt.audit_pages()
    finally:
        bt.close()


def test_serial_and_pipelined_resume_bitwise_equal(lm, tmp_path):
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (4,)).astype(np.int64)
    outs = []
    for name, (slots, chunk) in (("serial", (1, None)),
                                 ("pipelined", (3, 1))):
        bt = _tiered(lm, tmp_path / name, host_blocks=2, disk_blocks=6,
                     slots=slots, chunk=chunk)
        try:
            with paddle.no_grad():
                out1 = _run(bt, prompt, 6)
                bt.pause_session("conv", out1)
                _churn(bt)
                toks = bt.resume_session("conv")
                outs.append(_run(bt, np.concatenate([toks, cont]), 6))
                bt.audit_pages()
        finally:
            bt.close()
    np.testing.assert_array_equal(outs[0], outs[1])


def test_torn_publish_drill_full_reprefill_token_exact(lm, tmp_path):
    """Replica A's publish tears mid-write and A dies. Replica B shares
    only the store: the resume finds debris (typed finding), degrades to
    a full re-prefill from the caller's context, token-exact."""
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    a = _tiered(lm, tmp_path)
    try:
        with paddle.no_grad():
            out1 = _run(a, prompt, 6)
            arm_scenario(
                "seed=0; kv.session_publish:torn_write:offset=40,count=1")
            assert a.pause_session("conv", out1) is False
            assert a.session_store.findings[-1].kind == "publish_torn"
    finally:
        a.close()
    disarm()
    b = _tiered(lm, tmp_path)                # fresh process, same volume
    try:
        with paddle.no_grad():
            assert b.resume_session("conv") is None
            assert b.session_store.findings[-1].kind == "torn_manifest"
            # caller's fallback context -> full prefill, still exact
            out2 = _run(b, np.concatenate([out1, cont]), 6)
            np.testing.assert_array_equal(
                out2, _ref(lm, np.concatenate([out1, cont]), 6))
            b.audit_pages()
    finally:
        b.close()


# -- transfer plumbing --------------------------------------------------------

def test_async_loader_close_cancels_queued_deterministically():
    """The satellite-1 contract: close() fails every QUEUED transfer
    with TransferCancelled (never issued, device untouched) while the
    in-flight one lands normally."""
    from paddle_tpu.perf.prefetch import AsyncLoader, TransferCancelled
    ld = AsyncLoader(depth=4, workers=1)
    gate, started = threading.Event(), threading.Event()

    def slow():
        started.set()
        assert gate.wait(10.0)
        return [np.arange(3, dtype=np.float32)]

    f1 = ld.submit(slow)
    assert started.wait(10.0)                # worker is INSIDE f1
    f2 = ld.submit([np.ones(2, np.float32)])
    f3 = ld.submit([np.ones(4, np.float32)])
    opener = threading.Timer(0.15, gate.set)
    opener.start()
    try:
        ld.close(timeout=10.0)
    finally:
        opener.join()
    for f in (f2, f3):
        with pytest.raises(TransferCancelled):
            f.result(timeout=1.0)
    np.testing.assert_array_equal(
        np.asarray(f1.result(timeout=1.0)[0]), np.arange(3))
    assert not any(t.is_alive() for t in ld._threads)


# -- fleet drills -------------------------------------------------------------

def _gateway(lm, tmp, names=("r0", "r1")):
    from paddle_tpu.inference.gateway import Gateway
    gw = Gateway(policy="affinity",
                 session_store=os.path.join(str(tmp), "sessions"))
    for i, name in enumerate(names):
        gw.add_replica(name, _tiered(lm, os.path.join(str(tmp), name)))
    return gw


def _close_fleet(gw):
    for r in gw.pool.replicas():
        if r.alive:
            r.batcher.close()


def test_acceptance_drill_kill_rescale_resume_bitwise(lm, tmp_path):
    """THE acceptance drill: pause a session, kill its replica, rescale
    the fleet, resume — the resumed turn is bitwise identical to the
    uninterrupted conversation and no survivor leaks a page."""
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    base1 = _ref(lm, prompt, 6)
    base2 = _ref(lm, np.concatenate([base1, cont]), 6)

    gw = _gateway(lm, tmp_path)
    with paddle.no_grad():
        gid = gw.submit(prompt, 6, session_id="conv")
        while gw._has_work():
            gw.step()
        np.testing.assert_array_equal(gw.pop_result(gid), base1)
        assert gw.pause_session("conv") is True
        victim = gw._session_last_replica["conv"]
        assert "conv" in gw.pool.get(victim).batcher._session_pins

        # the pinned replica's host dies mid-request (the error kind
        # bypasses the retry policy; prefix affinity routes this
        # throwaway onto the replica holding the session's chain, and
        # its requeue lands on the survivor)
        arm_scenario(f"seed=0; gateway.step.{victim}:transient_error"
                     f":count=1")
        doomed = gw.submit(prompt, 4)
        for _ in range(2000):
            gw.step()
            if not gw.pool.get(victim).alive:
                break
        disarm()
        assert not gw.pool.get(victim).alive
        while gw._has_work():
            gw.step()
        gw.pop_result(doomed)

        gw.add_replica("r2", _tiered(lm, tmp_path / "r2"))   # rescale
        gid2 = gw.resume_session("conv", new_tokens=cont,
                                 max_new_tokens=6)
        while gw._has_work():
            gw.step()
        np.testing.assert_array_equal(gw.pop_result(gid2), base2)
        assert gw.stats()["failures"] == 0
        for r in gw.pool.replicas():
            if r.alive:
                r.batcher.audit_pages()      # raises on any leaked page
    _close_fleet(gw)


def test_mid_promotion_replica_kill_survivor_finishes(lm, tmp_path):
    """Kill the session's replica WHILE its resume promotion is in
    flight: the request requeues and the survivor finishes it by full
    prefill, token-exact."""
    rng = np.random.RandomState(19)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    base1 = _ref(lm, prompt, 6)
    base2 = _ref(lm, np.concatenate([base1, cont]), 6)

    gw = _gateway(lm, tmp_path)
    with paddle.no_grad():
        gid = gw.submit(prompt, 6, session_id="conv")
        while gw._has_work():
            gw.step()
        np.testing.assert_array_equal(gw.pop_result(gid), base1)
        gw.pause_session("conv")
        victim = gw._session_last_replica["conv"]
        vb = gw.pool.get(victim).batcher
        with paddle.no_grad():
            _churn(vb)                       # demote the pinned chain
        assert any(n.residency != "device"
                   for n in vb._session_pins["conv"])

        # affinity routes the resume back to ``victim``; its first step
        # opens the promotion stream, the second kills the host under it
        arm_scenario(f"seed=0; gateway.step.{victim}:transient_error"
                     f":after=1,count=1")
        gid2 = gw.resume_session("conv", new_tokens=cont,
                                 max_new_tokens=6)
        for _ in range(4000):
            if not gw._has_work():
                break
            gw.step()
        disarm()
        assert not gw.pool.get(victim).alive
        s = gw.stats()
        assert s["requeued"] > 0 and s["failures"] == 0
        np.testing.assert_array_equal(gw.pop_result(gid2), base2)
        for r in gw.pool.replicas():
            if r.alive:
                r.batcher.audit_pages()
    _close_fleet(gw)


def test_drain_requeue_preserves_session_pins(lm, tmp_path):
    """Remediation's drain path must not orphan paused sessions: pins
    survive the drain and a later resume on the drained replica's warm
    cache still works."""
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (4,)).astype(np.int64)
    gw = _gateway(lm, tmp_path)
    with paddle.no_grad():
        gid = gw.submit(prompt, 6, session_id="conv")
        while gw._has_work():
            gw.step()
        out1 = gw.pop_result(gid)
        gw.pause_session("conv")
        victim = gw._session_last_replica["conv"]
        gw.drain_replica(victim, requeue=True)
        assert "conv" in gw.pool.get(victim).batcher._session_pins
        gid2 = gw.resume_session("conv", new_tokens=cont,
                                 max_new_tokens=6)
        while gw._has_work():
            gw.step()
        np.testing.assert_array_equal(
            gw.pop_result(gid2),
            _ref(lm, np.concatenate([out1, cont]), 6))
    _close_fleet(gw)


def test_fresh_gateway_resolves_session_from_manifest_alone(lm, tmp_path):
    """Replica-independence: a gateway process that never served the
    session (no local record, no fallback) resumes it purely from the
    shared manifest."""
    rng = np.random.RandomState(29)
    prompt = rng.randint(0, 128, (48,)).astype(np.int64)
    cont = rng.randint(0, 128, (5,)).astype(np.int64)
    gw1 = _gateway(lm, tmp_path, names=("a0",))
    with paddle.no_grad():
        gid = gw1.submit(prompt, 6, session_id="conv")
        while gw1._has_work():
            gw1.step()
        out1 = gw1.pop_result(gid)
        assert gw1.pause_session("conv") is True
    _close_fleet(gw1)

    gw2 = _gateway(lm, tmp_path, names=("b0",))   # same shared volume
    with paddle.no_grad():
        gid2 = gw2.resume_session("conv", new_tokens=cont,
                                  max_new_tokens=6)
        while gw2._has_work():
            gw2.step()
        np.testing.assert_array_equal(
            gw2.pop_result(gid2),
            _ref(lm, np.concatenate([out1, cont]), 6))
    _close_fleet(gw2)


# -- tooling ------------------------------------------------------------------

def test_traffic_agentic_population_deterministic_and_audited(lm,
                                                              tmp_path):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import traffic
    finally:
        sys.path.pop(0)
    spec = traffic.TrafficSpec(
        seed=5, steps=8, vocab=128, base_rate=0.4, pattern="steady",
        prompt_lo=8, prompt_hi=20, new_lo=4, new_hi=6, shared_frac=0.0,
        session_frac=0.0, agentic_frac=1.0, agentic_turns_lo=1,
        agentic_turns_hi=2, agentic_gap_lo=1, agentic_gap_hi=3,
        agentic_cont_lo=3, agentic_cont_hi=5)
    a, b = traffic.generate(spec), traffic.generate(spec)
    flat_a = [r for step in a for r in step]
    flat_b = [r for step in b for r in step]
    assert [r.session_id for r in flat_a] == [r.session_id
                                              for r in flat_b]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(flat_a, flat_b))
    assert all(r.session_id.startswith("agent") and r.turns_left >= 1
               for r in flat_a)
    assert sum(r.turns_left for r in flat_a) > 0

    gw = _gateway(lm, tmp_path, names=("r0",))
    try:
        with paddle.no_grad():
            res = traffic.drive(gw, a, ttft_slo_s=60.0,
                                exact_ref=lambda p, n: _ref(lm, p, n))
    finally:
        _close_fleet(gw)
    assert res.resumed > 0
    assert res.resume_exact == res.resumed
    assert res.resume_mismatch == 0 and res.failed == 0
    assert res.summary()["resumed"] == res.resumed
    _close_fleet(gw)


def test_telemetry_dump_sessions_timeline(tmp_path, monkeypatch,
                                          capsys):
    from paddle_tpu.observability import fleet
    monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
    fleet.reset_spool()
    try:
        fleet.spool_event("session", op="publish", session="conv",
                          blocks=3, tokens=54)
        fleet.spool_event("session", op="finding", session="conv",
                          finding="torn_manifest", detail="tmp debris")
        fleet.spool_event("session", op="resume", session="conv",
                          source="manifest", tokens=59, gid=4)
    finally:
        fleet.reset_spool()
    spec = importlib.util.spec_from_file_location(
        "telemetry_dump", os.path.join(REPO, "tools",
                                       "telemetry_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--fleet", str(tmp_path), "--sessions"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "# session timeline" in out
    assert "publish" in out and "resume" in out
    assert "1 finding(s)" in out and "torn_manifest" in out


def test_session_inspect_cli_verdicts_on_a_real_store(tmp_path, capsys):
    store = SessionStore(str(tmp_path))
    store.publish(SessionManifest(session_id="good",
                                  token_ids=list(range(48)),
                                  block_size=16))
    store.publish(SessionManifest(session_id="bad",
                                  token_ids=list(range(32)),
                                  block_size=16))
    p = store.path_for("bad")
    doc = json.loads(open(p, "rb").read())
    doc["tokens"][0] ^= 1
    open(p, "wb").write(json.dumps(doc, sort_keys=True).encode())
    spec = importlib.util.spec_from_file_location(
        "session_inspect", os.path.join(REPO, "tools",
                                        "session_inspect.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 2
    assert "BAD" in out and "sound manifests: 1/2" in out
    # the offline recompute agrees with the store's own validator
    rep = mod.inspect_root(str(tmp_path))
    assert {r["session"]: r["ok"] for r in rep["manifests"]} == {
        "good": True, "bad": False}


def test_bench_guard_session_lane_gates_goodput(tmp_path):
    import subprocess
    hist = [510.0, 540.0, 555.0, 566.0]
    for i, v in enumerate(hist, start=2):
        (tmp_path / f"BENCH_SESSION_r{i:02d}.json").write_text(
            json.dumps({"metric": "session_resume_goodput", "value": v,
                        "unit": "tokens/s",
                        "detail": {"tpu": False,
                                   "time_to_resume_ms": 400.0 - 4 * i}}))

    def guard(args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "bench_guard.py")] + args,
            capture_output=True, text=True)

    ok = guard(["--check", "--dir", str(tmp_path), "--json"])
    assert ok.returncode == 0, ok.stdout + ok.stderr
    report = json.loads(ok.stdout)
    key = "session:session_resume_goodput/cpu"
    assert report["series"][key]["status"] == "pass"
    assert all(k.startswith("session:") for k in report["series"])
    # a 20% goodput collapse (and the slower resume behind it) gates
    (tmp_path / "BENCH_SESSION_r06.json").write_text(
        json.dumps({"metric": "session_resume_goodput",
                    "value": 0.8 * hist[-1], "unit": "tokens/s",
                    "detail": {"tpu": False,
                               "time_to_resume_ms": 520.0}}))
    bad = guard(["--check", "--dir", str(tmp_path), "--json"])
    assert bad.returncode == 1
    assert json.loads(bad.stdout)["series"][key]["status"] == \
        "regression"
