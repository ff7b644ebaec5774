"""The clock of the serving drills (tests/test_selfheal.py and
tests/test_attribution.py's failover case).

What those drills assert (which replica the detector names, whether a
quiet fleet stays quiet, how many steps TTFT needs to come back inside
its objective) follows from the schedule, which is deterministic, and
from what a step costs. Read off the wall clock, the cost was whatever
five other workers left this one: a stalled step looked like a
straggler, a slow machine like a breached objective. So the drills own
the time. A replica's step costs what it computes, and a chaos ``delay``
costs its ``delay_s``; nothing else moves the clock.
"""
import paddle_tpu.inference.gateway.gateway as _gateway
import paddle_tpu.inference.gateway.replica as _replica
import paddle_tpu.inference.gateway.router as _router
import paddle_tpu.observability.anomaly as _anomaly
import paddle_tpu.resilience.chaos as _chaos


class DrillClock:
    """Stands in for the ``time`` module where the gateway stamps
    requests, the probe times steps and chaos sleeps."""

    # a tiny model's step is its dispatches on any machine: one to decode
    # the slots, one for each request admitted (its prefill); the rows a
    # dispatch computes add a little
    DISPATCH_S = 0.01
    ROW_S = DISPATCH_S / 128

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    monotonic = perf_counter

    def sleep(self, seconds):
        self.now += seconds

    def install(self, monkeypatch, gw):
        """Before a ``GatewayProbe`` wraps ``gw``: the step's cost has to
        fall inside the window the probe times."""
        for mod in (_gateway, _replica, _router, _anomaly):
            monkeypatch.setattr(mod, "_time", self)
        monkeypatch.setattr(_chaos, "time", self)
        step_replica = gw.pool.step_replica

        def stepped(rep):
            b = rep.batcher
            admitted = list(b._pending)[:len(b._free)]
            rows = sum(len(r.prompt) for r in admitted) + len(b._slot_req)
            self.now += (self.DISPATCH_S * (1 + len(admitted))
                         + self.ROW_S * rows)
            return step_replica(rep)

        gw.pool.step_replica = stepped
        return self
