"""Self-healing fleet drills: the telemetry -> remediation loop
(paddle_tpu.resilience.remediator + gateway.autoscaler) under the
deterministic traffic harness (benchmarks/traffic.py).

The acceptance bars:
  * a chaos straggler delay on ONE replica makes the remediator NAME
    and drain exactly that replica (token-exact requeue: every request
    still completes), and TTFT returns in-SLO within a bounded number
    of steps after the drain;
  * the identical schedule with NO fault executes ZERO actions (the
    loop is quiet on a healthy fleet);
  * hysteresis means K CONSECUTIVE firings — one isolated spike never
    drains anything;
  * the per-(action, target) cooldown forbids drain -> drain churn on
    one replica, and the global flap guard escalates (freeze doubling)
    instead of oscillating under a persistent fault;
  * the autoscaler rides the existing drain/remove lifecycle: scale-up
    under queue pressure, scale-down drains (not kills) its own
    addition once idle.

Everything is single-threaded and deterministic, time included: the two
drills run on tests/drill_clock.py's clock, where a step costs what it
computes and a chaos delay its ``delay_s``.
"""
import os
import sys
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.gateway import Autoscaler, Gateway
from paddle_tpu.inference.serving import ContinuousBatcher
from paddle_tpu.observability.anomaly import AnomalyDetector, GatewayProbe
from paddle_tpu.observability.fleet import FleetFinding
from paddle_tpu.resilience import arm_scenario, disarm
from paddle_tpu.resilience.remediator import (AutoRemediator, FlapGuard,
                                              PolicyRule,
                                              remediate_enabled)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
import traffic  # noqa: E402
from drill_clock import DrillClock  # noqa: E402

pytestmark = pytest.mark.selfheal

# separation, in DrillClock's seconds: an honest step that admits k
# requests costs 1 + k dispatches, a robust z of 20 k against a baseline
# of decode-only steps (the detector floors its scale at 5% of the
# median), and a step admits at most its 8 slots; the injected delay is
# 40 dispatches, a z of 800. The detector threshold sits between. The
# TTFT SLO is one honest traffic meets (a gateway step is 0.02 to 0.06 s)
# and the straggler breaks
DETECT_Z = 200.0
TTFT_SLO_S = 0.15
STRAGGLE_S = 0.4


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


def _factory(lm):
    # batch headroom matters for the drill: after the straggler drains,
    # ONE survivor must absorb the requeued load with slack (throughput
    # 8 slots / ~7 steps-per-request >> 0.5 arrivals/step), else queue
    # wait alone breaches the TTFT SLO forever
    def make(name):
        return ContinuousBatcher(lm, max_batch=8, s_max=96,
                                 compile=False)
    return make


def _spec(**kw):
    kw.setdefault("seed", 5)
    kw.setdefault("steps", 30)
    kw.setdefault("vocab", 128)
    # light enough that ONE replica sustains it in-SLO (post-drain the
    # drill must recover, not drown the survivor in queueing TTFT) but
    # with requests long enough that a loaded replica stays busy on
    # CONSECUTIVE ticks — sparse one-shot work can never meet hysteresis
    kw.setdefault("base_rate", 0.5)
    kw.setdefault("prompt_lo", 6)
    kw.setdefault("prompt_hi", 16)
    kw.setdefault("new_lo", 5)
    kw.setdefault("new_hi", 8)
    kw.setdefault("shared_len", 12)
    return traffic.TrafficSpec(**kw)


def _rig(lm, policy, monkeypatch):
    """Gateway + probe/detector/remediator on the drill's clock,
    baselines warmed on healthy steps (chaos arms AFTER this returns)."""
    make = _factory(lm)
    gw = Gateway(policy="least_loaded", max_queue_depth=128)
    gw.add_replica("r0", make("r0"))
    gw.add_replica("r1", make("r1"))
    clock = DrillClock().install(monkeypatch, gw)
    detector = AnomalyDetector(threshold=DETECT_Z, min_samples=8)
    probe = GatewayProbe(gw, detector)
    rem = AutoRemediator(gw, detector=detector, policy=policy,
                         replica_factory=make, clock=clock.monotonic,
                         flap_guard=FlapGuard(max_actions=4,
                                              window_s=30.0,
                                              clock=clock.monotonic))
    rng = np.random.RandomState(7)
    # Loop until BOTH replicas' detector series are past warmup —
    # routing does not split work evenly on small batches.
    for _ in range(8):
        for n in (6, 10, 20, 28):
            gw.submit(rng.randint(0, 128, (n,)), 4, tenant="warmup")
        gw.run_until_done()
        if all((t := detector._tracks.get(("tpot", r))) is not None
               and t.count >= detector.min_samples + 2
               for r in ("r0", "r1")):
            break
    gw.reset_stats()
    return gw, rem, probe


DRAIN_POLICY = (PolicyRule("tpot_spike", "drain_replica", hysteresis=2,
                           cooldown_s=30.0),)


# -- the chaos drill ----------------------------------------------------------

def test_straggler_drill_names_and_drains_the_right_replica(
        lm, monkeypatch):
    """One replica goes slow; the loop drains THAT replica and TTFT
    returns in-SLO within a bounded number of steps of the action."""
    gw, rem, probe = _rig(lm, DRAIN_POLICY, monkeypatch)
    arm_scenario(f"seed=0; gateway.step.r1:delay:"
                 f"delay_s={STRAGGLE_S},after=1,count=10000")
    drain_step = []

    def tick(step):
        for act in rem.tick():
            if act.executed and not drain_step:
                drain_step.append(step)
    try:
        res = traffic.drive(gw, traffic.generate(_spec()), TTFT_SLO_S,
                            tick=tick)
    finally:
        disarm()
        probe.close()

    executed = rem.executed()
    assert executed, "remediator never acted on the straggler"
    assert all(a.kind == "drain_replica" and a.target == "r1"
               for a in executed), \
        f"wrong action(s): {[(a.kind, a.target) for a in executed]}"
    assert len(executed) == 1          # once — no churn on one fault
    # the drained replica left the routable set but was NOT killed
    rep = gw.pool.get("r1")
    assert rep.alive and not rep.routable()
    # token-exactness: drive() raises on any lost/duplicated token
    # through the drain requeue, so completing the schedule IS the
    # proof; nothing may be lost outright either
    assert res.failed == 0 and res.completions == res.submitted
    # recovery: once the straggler is out, completions return in-SLO
    # within a bounded window (delayed stragglers already in flight
    # still finish late — allow them to clear)
    assert res.first_breach_step is not None
    assert drain_step, "no executed action step recorded"
    assert res.last_breach_step <= drain_step[0] + 25, (
        f"TTFT never recovered: drained at step {drain_step[0]}, "
        f"last breach at {res.last_breach_step}")


def test_no_fault_control_run_takes_zero_actions(lm, monkeypatch):
    """The IDENTICAL schedule with no chaos: a quiet loop."""
    gw, rem, probe = _rig(lm, DRAIN_POLICY, monkeypatch)
    try:
        res = traffic.drive(gw, traffic.generate(_spec()), TTFT_SLO_S,
                            tick=lambda s: rem.tick())
    finally:
        probe.close()
    assert rem.executed() == []
    assert res.failed == 0 and res.completions == res.submitted
    assert len(gw.pool.routable()) == 2


# -- gating: hysteresis, cooldown, flap guard ---------------------------------

def _stub_detector():
    return types.SimpleNamespace(findings=[])


def _spike(seq, key="r1"):
    return FleetFinding(kind="tpot_spike", op="tpot", seq=seq,
                        detail={"key": key, "score": 9.9})


def _bare_gateway(lm):
    make = _factory(lm)
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", make("r0"))
    gw.add_replica("r1", make("r1"))
    return gw, make


def test_single_spike_below_hysteresis_never_acts(lm):
    gw, make = _bare_gateway(lm)
    det = _stub_detector()
    rem = AutoRemediator(gw, detector=det, policy=DRAIN_POLICY,
                         replica_factory=make, clock=lambda: 0.0)
    det.findings.append(_spike(1))
    assert rem.tick(now=0.0) == []          # streak 1 < hysteresis 2
    rem.tick(now=1.0)                       # quiet tick resets streak
    det.findings.append(_spike(2))
    assert rem.tick(now=2.0) == []          # streak back to 1
    assert rem.executed() == []
    assert gw.pool.get("r1").routable


def test_consecutive_spikes_drain_then_cooldown_suppresses_churn(lm):
    gw, make = _bare_gateway(lm)
    det = _stub_detector()
    rem = AutoRemediator(gw, detector=det, policy=DRAIN_POLICY,
                         replica_factory=make, clock=lambda: 0.0)
    det.findings.append(_spike(1))
    rem.tick(now=0.0)
    det.findings.append(_spike(2))
    acts = rem.tick(now=1.0)
    assert [a.decision for a in acts] == ["executed"]
    assert acts[0].target == "r1"
    assert not gw.pool.get("r1").routable()
    # the same signal keeps firing inside the 30s cooldown: decided
    # but suppressed — the replica is never drained twice
    for t in (2.0, 3.0):
        det.findings.append(_spike(10 + int(t)))
        det.findings.append(_spike(11 + int(t)))
        for a in rem.tick(now=t):
            assert a.decision == "cooldown"
    assert len(rem.executed()) == 1


def test_last_routable_replica_is_never_drained(lm):
    gw, make = _bare_gateway(lm)
    det = _stub_detector()
    rem = AutoRemediator(gw, detector=det, policy=DRAIN_POLICY,
                         replica_factory=make, clock=lambda: 0.0)
    gw.drain_replica("r0")                  # only r1 left routable
    det.findings.append(_spike(1))
    rem.tick(now=0.0)
    det.findings.append(_spike(2))
    acts = rem.tick(now=1.0)
    assert [a.decision for a in acts] == ["last_replica"]
    assert gw.pool.get("r1").routable()


def test_flap_guard_escalates_instead_of_oscillating():
    t = [0.0]
    g = FlapGuard(max_actions=2, window_s=10.0, freeze_s=20.0,
                  clock=lambda: t[0])
    assert g.check()[0]
    g.record()
    t[0] = 1.0
    assert g.check()[0]
    g.record()
    t[0] = 2.0
    ok, why = g.check()
    assert (ok, why) == (False, "flap_budget")     # budget spent
    assert g.frozen_until == pytest.approx(22.0)   # frozen 20s
    t[0] = 10.0
    assert g.check() == (False, "flap_frozen")
    # past the freeze AND the window pruned the old actions: allowed
    # (but NOT calm yet — frozen time does not count toward re-arming)
    t[0] = 23.0
    assert g.check()[0]
    # a second breach before a full calm window doubles the freeze
    g.record()
    t[0] = 23.5
    g.record()
    t[0] = 24.0
    ok, why = g.check()
    assert (ok, why) == (False, "flap_budget")
    assert g.escalations == 2
    assert g.frozen_until == pytest.approx(24.0 + 40.0)  # 20 * 2


def test_remediator_freezes_under_oscillating_fault(lm):
    """A fault that keeps re-firing across targets hits the flap budget
    and the remediator FREEZES (escalate-don't-oscillate) rather than
    draining/restoring forever."""
    gw, make = _bare_gateway(lm)
    for n in ("r2", "r3", "r4"):
        gw.add_replica(n, make(n))
    det = _stub_detector()
    policy = (PolicyRule("tpot_spike", "drain_replica", hysteresis=1,
                         cooldown_s=0.5),)
    guard = FlapGuard(max_actions=2, window_s=60.0, freeze_s=120.0,
                      clock=lambda: 0.0)
    rem = AutoRemediator(gw, detector=det, policy=policy,
                         replica_factory=make, flap_guard=guard,
                         clock=lambda: 0.0)
    seq = [0]

    def fire(key, now):
        seq[0] += 1
        det.findings.append(_spike(seq[0], key=key))
        return rem.tick(now=now)

    assert fire("r0", 0.0)[0].executed
    assert fire("r1", 1.0)[0].executed
    # budget (2 per window) spent: every further proposal is rejected,
    # the guard freezes, and NOTHING else is drained
    decisions = [a.decision for now, key in ((2.0, "r2"), (3.0, "r3"))
                 for a in fire(key, now)]
    assert decisions and all(d in ("flap_budget", "flap_frozen")
                             for d in decisions)
    assert len(rem.executed()) == 2
    assert len(gw.pool.routable()) == 3
    assert rem.summary()["flap_escalations"] >= 1


# -- autoscaler lifecycle -----------------------------------------------------

def test_autoscaler_scales_up_under_queue_pressure_and_drains_back(lm):
    gw, make = _bare_gateway(lm)
    t = [0.0]
    asc = Autoscaler(gw, make, min_replicas=2, max_replicas=3,
                     queue_high=4, queue_low=0, hysteresis=2,
                     cooldown_s=1.0, clock=lambda: t[0])
    rng = np.random.RandomState(3)
    for _ in range(12):
        gw.submit(rng.randint(0, 128, (8,)), 4)
    assert asc.tick() is None               # streak 1
    t[0] = 2.0
    assert asc.tick() == "scale_up:auto0"   # streak 2 -> add
    assert "auto0" in gw.pool
    gw.run_until_done()
    # idle now: two consecutive low-pressure ticks past cooldown drain
    # the addition back out through the normal lifecycle
    t[0] = 4.0
    assert asc.tick() is None
    t[0] = 6.0
    assert asc.tick() == "scale_down:auto0"
    gw.run_until_done()
    t[0] = 8.0
    asc.tick()                              # _finalize removes it
    assert "auto0" not in gw.pool
    assert len(gw.pool.routable()) == 2


def test_remediate_env_gate(monkeypatch):
    monkeypatch.setenv("PADDLE_REMEDIATE", "0")
    assert not remediate_enabled()
    monkeypatch.setenv("PADDLE_REMEDIATE", "dry")
    assert remediate_enabled()
    monkeypatch.delenv("PADDLE_REMEDIATE")
    assert remediate_enabled()


def test_dry_run_journals_but_never_touches_the_pool(lm):
    gw, make = _bare_gateway(lm)
    det = _stub_detector()
    rem = AutoRemediator(gw, detector=det, policy=DRAIN_POLICY,
                         replica_factory=make, dry_run=True,
                         clock=lambda: 0.0)
    det.findings.append(_spike(1))
    rem.tick(now=0.0)
    det.findings.append(_spike(2))
    acts = rem.tick(now=1.0)
    assert [a.decision for a in acts] == ["dry_run"]
    assert gw.pool.get("r1").routable()
    assert rem.executed() == []
