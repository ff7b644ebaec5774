"""Continuous batching over the KV-cache decode step.

Reference surface: the serving loop the reference builds around
AnalysisPredictor + block_multihead_attention (dynamic request admission
into a running decode batch). TPU-first design: XLA wants ONE static
shape, so the batcher owns `max_batch` SLOTS — a fixed [L, 2, B, H, S, D]
cache — and the host-side scheduler admits pending requests into free
slots at step boundaries, evicts finished ones, and steps every slot
through one compiled decode executable. Inactive slots decode garbage
into a scratch row that admission's prefill overwrites before any real
read (causality: a slot's attention never reads rows past its own t), so
no per-occupancy recompilation ever happens.
"""
from __future__ import annotations

import os as _os
import time as _time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..observability.tracing import span as _span

__all__ = ["ContinuousBatcher", "PagedContinuousBatcher", "Request"]


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [s] int64
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    # explicit flag: a PREEMPTED request also has slot None + partial
    # tokens while it waits for re-admission — it is not done
    finished: bool = False
    submit_t: float = 0.0       # perf_counter at submit (TTFT anchor)
    deadline_t: Optional[float] = None  # perf_counter; None = no deadline
    # propagated request trace (observability.trace_context): the
    # gateway mints it; the batcher opens admit/prefill/decode spans
    # under it; ``spans`` holds the OPEN ones so abort paths can close
    trace: Optional[object] = None
    spans: Dict[str, object] = field(default_factory=dict)

    @property
    def done(self) -> bool:
        return self.finished


class _ServingStats:
    """Per-batcher serving telemetry, reported TWICE: local counters keep
    the ``stats()`` contract exact per instance (and resettable after
    warmup), while every event also lands in the process-wide metrics
    registry as ``serving_*`` series labeled by engine — the pipe the
    Prometheus/JSONL exporters and ``tools/telemetry_dump.py`` read."""

    def __init__(self, engine: str):
        from .. import observability as obs
        reg = obs.get_registry()
        eng = ("engine",)

        def c(name, help):
            return reg.counter(name, help, labelnames=eng).labels(
                engine=engine)

        def g(name, help):
            return reg.gauge(name, help, labelnames=eng).labels(
                engine=engine)

        def h(name, help):
            return reg.histogram(name, help, labelnames=eng).labels(
                engine=engine)

        self.requests = c("serving_requests_total", "requests submitted")
        self.admissions = c("serving_admissions_total",
                            "requests admitted into slots")
        self.completions = c("serving_completions_total",
                             "requests finished")
        self.preempt_c = c("serving_preemptions_total",
                           "requests preempted back to the queue")
        self.tokens_c = c("serving_tokens_total", "tokens generated")
        self.steps_c = c("serving_steps_total", "decode steps")
        self.queue_depth = g("serving_queue_depth",
                             "pending requests right now")
        self.active_slots = g("serving_active_slots",
                              "occupied slots right now")
        self.ttft = h("serving_ttft_seconds",
                      "submit to first generated token")
        self.step_seconds = h("serving_step_seconds",
                              "one decode dispatch wall time")
        self.shed_c = c("requests_shed_total",
                        "requests rejected at admission (queue full)")
        self.expired_c = c("serving_deadline_expired_total",
                           "requests abandoned on an expired deadline")
        self.reset()

    def reset(self):
        """Re-baseline the per-instance counters (the registry series are
        process-cumulative by design and keep running)."""
        self.steps = 0
        self.tokens = 0
        self.occupancy_sum = 0
        self.completed = 0
        self.preempted = 0
        self.cachekv_elems = 0
        self.cachekv_clipped = 0
        self.warned_cachekv_clip = False
        self.shed = 0
        self.expired = 0
        self.t0 = _time.perf_counter()

    # -- events -------------------------------------------------------------
    def on_submit(self, pending_now: int):
        self.requests.inc()
        self.queue_depth.set(pending_now)

    def on_admit(self):
        self.admissions.inc()

    def on_token(self, req: Request):
        self.tokens += 1
        self.tokens_c.inc()
        if len(req.tokens) == 1 and req.submit_t:
            self.ttft.observe(_time.perf_counter() - req.submit_t)

    def on_step(self):
        self.steps += 1
        self.steps_c.inc()

    def on_occupancy(self, n: int):
        self.occupancy_sum += n

    def on_decode_time(self, dt: float, tokens: int = 0):
        self.step_seconds.observe(dt)
        if tokens:
            # join the dispatch against the roofline's serving token
            # bound (roofline.serving.* gauges; no-op without a model)
            from ..observability import roofline_attr
            roofline_attr.observe_serving_step(dt, tokens)

    def on_complete(self):
        self.completed += 1
        self.completions.inc()

    def on_preempt(self):
        self.preempted += 1
        self.preempt_c.inc()

    def on_shed(self):
        self.shed += 1
        self.shed_c.inc()

    def on_deadline_expired(self):
        self.expired += 1
        self.expired_c.inc()

    def on_cachekv(self, clipped: int, total: int):
        self.cachekv_elems += total
        self.cachekv_clipped += clipped

    def set_gauges(self, pending: int, active: int):
        self.queue_depth.set(pending)
        self.active_slots.set(active)

    # -- the stats() contract -----------------------------------------------
    def snapshot(self, max_batch: int, pending: int,
                 active: int) -> Dict[str, float]:
        dt = max(_time.perf_counter() - self.t0, 1e-9)
        steps = max(self.steps, 1)
        return {
            "steps": self.steps,
            "generated_tokens": self.tokens,
            "tokens_per_sec": self.tokens / dt,
            "mean_active_slots": self.occupancy_sum / steps,
            "slot_utilization": self.occupancy_sum / steps / max_batch,
            "completed_requests": self.completed,
            "preemptions": self.preempted,
            "pending_now": pending,
            "active_now": active,
            "elapsed_s": dt,
            "cachekv_clip_rate": (self.cachekv_clipped
                                  / max(self.cachekv_elems, 1)),
            "requests_shed": self.shed,
            "deadline_expired": self.expired,
        }


class _BatcherBase:
    """Request lifecycle shared by the dense-slot and paged batchers:
    FIFO submission, finish-on-EOS-or-budget, result retrieval, deadline
    expiry + load shedding, health reporting, and the drive loop.
    Subclasses own the cache layout and implement ``_release_slot(slot)``
    (return the slot's memory to their pool) plus ``_step_impl()`` (one
    engine step; the base ``step()`` wraps it with deadline/health/chaos
    policy)."""

    _engine = "serving"        # registry label; subclasses override

    def _init_queues(self, max_queue_depth: Optional[int] = None,
                     default_deadline_s: Optional[float] = None):
        self._slot_req: Dict[int, Request] = {}
        self._pending: List[Request] = []
        self._finished: Dict[int, Request] = {}
        self._failed: Dict[int, Exception] = {}
        self._next_rid = 0  # tpu-lint: disable=CC404 (ctor-time init)
        # intake lock: serializes submit-side producers (a fronting RPC
        # layer may call submit/cancel off-thread) against the step
        # loop's queue harvest. Slot/device/cache state stays step-loop-
        # owned and is deliberately NOT under this lock — holding it
        # across prefill/decode would block every submitter for a full
        # device dispatch (CC402). Reentrant: submit and the step loop
        # both nest _expire_pending.
        from ..utils.locks import TracedRLock
        self._intake = TracedRLock("Batcher._intake")
        self._max_queue_depth = max_queue_depth
        self._default_deadline_s = default_deadline_s
        # serving observability (reference analog: the predictor's
        # benchmark counters): per-instance totals via stats(), process-
        # wide serving_* series via the observability registry
        self._tele = _ServingStats(self._engine)
        from ..resilience.recovery import HealthStateMachine
        self.health = HealthStateMachine(
            capacity=max_queue_depth or 2 * self.max_batch,
            engine=self._engine)

    def reset_stats(self):
        """Zero the counters and restart the clock — call after warmup so
        steady-state throughput excludes compile time."""
        self._tele.reset()

    def stats(self) -> Dict[str, float]:
        """Throughput/occupancy counters for monitoring: decode steps,
        generated tokens, tokens/sec since construction, mean active
        slots per step, utilization (active/max_batch), completions,
        preemptions, queue depth right now."""
        return self._tele.snapshot(self.max_batch, len(self._pending),
                                   len(self._slot_req))

    # back-compat handles: these private counters moved into _ServingStats;
    # external probes (tests, notebooks) still reach them at the old names
    @property
    def _stat_cachekv_elems(self) -> int:
        return self._tele.cachekv_elems

    @property
    def _stat_cachekv_clipped(self) -> int:
        return self._tele.cachekv_clipped

    @property
    def _warned_cachekv_clip(self) -> bool:
        return self._tele.warned_cachekv_clip

    @_warned_cachekv_clip.setter
    def _warned_cachekv_clip(self, v: bool):
        self._tele.warned_cachekv_clip = v

    @staticmethod
    def _check_window(cfg, s_max: int):
        if s_max > cfg.max_position_embeddings:
            raise ValueError(f"s_max={s_max} exceeds "
                             f"max_position_embeddings="
                             f"{cfg.max_position_embeddings}")

    def _validate(self, prompt: np.ndarray, max_new_tokens: int):
        if max_new_tokens < 1:
            # admission emits one token from the prefill logits, so a
            # zero-token request cannot match generate(max_new_tokens=0)
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.s_max:
            raise ValueError(f"prompt {len(prompt)} + {max_new_tokens} "
                             f"exceeds slot capacity {self.s_max}")

    def submit(self, prompt_ids, max_new_tokens: int,
               deadline_s: Optional[float] = None,
               trace: Optional[object] = None) -> int:
        """Queue a request. Raises typed ``Overloaded`` when the pending
        queue is at ``max_queue_depth`` (load shedding — a fronting layer
        maps it to 429). ``deadline_s`` (or the batcher's default) bounds
        the request's total latency: an expired request is abandoned at
        the next step boundary and its result() raises
        ``DeadlineExceeded``. ``trace`` (a ``TraceContext``) propagates a
        fronting layer's request trace: the batcher opens its
        admit/prefill/decode spans under it."""
        prompt = np.asarray(prompt_ids, np.int64).reshape(-1)
        self._validate(prompt, max_new_tokens)
        # purge already-expired queued requests BEFORE the capacity
        # check: a dead-on-arrival queue entry must not cause a shed
        # (shed and deadline_expired stay disjoint per request)
        self._expire_pending()
        shed_depth = None
        with self._intake:
            if self._max_queue_depth is not None \
                    and len(self._pending) >= self._max_queue_depth:
                shed_depth = len(self._pending)
            else:
                rid = self._next_rid
                self._next_rid += 1
                budget = deadline_s if deadline_s is not None \
                    else self._default_deadline_s
                now = _time.perf_counter()
                self._pending.append(Request(
                    rid, prompt, max_new_tokens, submit_t=now,
                    deadline_t=None if budget is None else now + budget,
                    trace=trace))
                depth = len(self._pending)
        # telemetry/health callbacks run OUTSIDE _intake (CC403): they
        # can re-enter the batcher or block on an exporter.
        if shed_depth is not None:
            from ..resilience.recovery import Overloaded
            self._tele.on_shed()
            self.health.on_shed()
            raise Overloaded(
                f"pending queue at capacity "
                f"({shed_depth}/{self._max_queue_depth})")
        self._tele.on_submit(depth)
        return rid

    # -- request-trace hooks (observability.trace_context) -------------------
    # All no-ops when the request carries no TraceContext (standalone
    # batchers, tracing disabled): one attribute check per event.
    def _trace_admit_begin(self, req: Request):
        if req.trace is not None:
            tags = {"engine": self._engine}
            group = getattr(self, "shard_group", None)
            if group is not None:
                # tensor-parallel group: name the members so the
                # waterfall shows WHICH shards this admit rode on
                tags["tp_group"] = group.name
                tags["tp_members"] = ",".join(group.members)
            req.spans["admit"] = req.trace.begin("admit", **tags)

    def _trace_prefill_begin(self, req: Request):
        if req.trace is not None:
            tags = {}
            if req.tokens:
                # preemption resume: this prefill recomputes KV the
                # eviction threw away (prompt + already-decoded tokens)
                tags["evict_recompute"] = 1
            elif req.trace.baggage.get("requeued"):
                # failover survivor: the prompt re-prefill duplicates
                # work the dead/drained replica already did — the ledger
                # costs this interval as waste.requeue_recompute
                tags["requeue_recompute"] = 1
                if req.trace.baggage.get("drained"):
                    # administrative drain, not a death — same recompute
                    # cost, different cause
                    tags["drain_recompute"] = 1
            req.spans["prefill"] = req.trace.begin(
                "prefill", parent=req.spans.get("admit"), **tags)

    def _trace_prefill_end(self, req: Request, **tags):
        sp = req.spans.pop("prefill", None)
        if sp is not None:
            sp.end(**tags)

    def _trace_admit_end(self, req: Request, slot: int):
        """Close the admit span and open the decode span (which stays
        open across batched steps until the request finishes)."""
        sp = req.spans.pop("admit", None)
        if sp is not None:
            sp.end(slot=slot)
        if req.trace is not None:
            req.spans["decode"] = req.trace.begin("decode", slot=slot)

    def _trace_close(self, req: Request, **tags):
        if req.spans:
            from ..observability.trace_context import end_open_spans
            end_open_spans(req.spans, **tags)

    def _fail(self, req: Request, exc: Exception):
        req.slot = None
        req.finished = True
        self._trace_close(req, error=type(exc).__name__)
        self._failed[req.rid] = exc

    def _expire_pending(self):
        """Abandon QUEUED requests whose deadline passed. Runs both at
        the step boundary and at submit time (before the capacity
        check), so an expired queue entry frees its spot instead of
        pushing a live request into a shed."""
        from ..resilience.recovery import DeadlineExceeded
        now = _time.perf_counter()
        with self._intake:
            expired = [r for r in self._pending
                       if r.deadline_t is not None and now > r.deadline_t]
            for req in expired:
                self._pending.remove(req)
        # fail/notify outside _intake: _fail closes the request trace and
        # on_deadline_expired is a telemetry callback (CC403)
        for req in expired:
            self._fail(req, DeadlineExceeded(
                f"request {req.rid} expired while queued"))
            self._tele.on_deadline_expired()

    def _expire_deadlines(self):
        """Abandon requests whose deadline passed — pending ones silently
        leave the queue, active ones release their slot (and cache
        memory) so live traffic gets the capacity back."""
        from ..resilience.recovery import DeadlineExceeded
        now = _time.perf_counter()

        def expired(r: Request) -> bool:
            return r.deadline_t is not None and now > r.deadline_t

        self._expire_pending()
        for slot, req in list(self._slot_req.items()):
            if expired(req):
                del self._slot_req[slot]
                self._release_slot(slot)
                self._fail(req, DeadlineExceeded(
                    f"request {req.rid} expired after "
                    f"{len(req.tokens)} tokens"))
                self._tele.on_deadline_expired()

    def step(self) -> List[int]:
        """Expire deadlines, then run one engine step (subclass
        ``_step_impl``); feeds the health state machine and the
        ``serving.step`` chaos point. Returns rids finishing during THIS
        call."""
        self._expire_deadlines()
        try:
            from ..resilience.chaos import fault_point
            fault_point("serving.step")
            group = getattr(self, "shard_group", None)
            if group is not None:
                # tensor-parallel shard group: a dead member means this
                # engine's weights/KV shard is gone — TPMemberDied is
                # non-retryable by design (the gateway declares the
                # whole group dead and requeues token-exact)
                group.heartbeat()
            finished = self._step_impl()
        except Exception:
            self.health.on_step_error()
            raise
        self.health.on_step_ok(len(self._pending))
        from ..observability.fleet import autospool_tick
        autospool_tick()   # rank-sharded metrics spool; no-op unarmed
        return finished

    def _pick(self, logits_np):
        """Next-token selection (greedy or sampled) on host logits [B, V];
        shares the model's sampling semantics."""
        from ..models.gpt import GPT2ForCausalLM
        return GPT2ForCausalLM._select_token(
            logits_np, self._do_sample, self._temperature, self._top_k,
            self._top_p, self._rng)

    def _maybe_finish(self, req: Request, tok: int) -> bool:
        if (tok == self.eos_id if self.eos_id is not None else False) \
                or len(req.tokens) >= req.max_new_tokens:
            slot = req.slot
            req.slot = None
            req.finished = True
            del self._slot_req[slot]
            self._release_slot(slot)
            self._trace_close(req, tokens=len(req.tokens))
            self._finished[req.rid] = req
            self._tele.on_complete()
            return True
        return False

    def _release_slot(self, slot: int):          # pragma: no cover
        raise NotImplementedError

    def result(self, rid: int) -> np.ndarray:
        """Full sequence (prompt + generated) of a finished request.
        Raises the request's typed failure (``DeadlineExceeded``) if it
        was abandoned instead of completed."""
        if rid in self._failed:
            raise self._failed[rid]
        req = self._finished[rid]
        return np.concatenate([req.prompt, np.asarray(req.tokens)])

    def pop_result(self, rid: int) -> np.ndarray:
        """result() + release the request's memory — long-lived batchers
        must pop (or use run_until_done, which pops) or _finished grows
        with every request ever served."""
        if rid in self._failed:
            raise self._failed.pop(rid)
        out = self.result(rid)
        del self._finished[rid]
        return out

    def _has_work(self) -> bool:
        return bool(self._pending or self._slot_req)

    def run_until_done(self, max_steps: int = 10000) -> Dict[int, np.ndarray]:
        """Drive until every submitted request completes; returns (and
        releases) exactly THIS run's results. Raises if the step budget
        is exhausted with work still pending/active — a silent partial
        dict would read as lost requests."""
        done: List[int] = []
        for _ in range(max_steps):
            done += self.step()
            if not self._has_work():
                break
        else:
            raise RuntimeError(
                f"run_until_done: {len(self._pending)} pending / "
                f"{len(self._slot_req)} active requests remain after "
                f"{max_steps} steps")
        return {rid: self.pop_result(rid) for rid in done}

    @property
    def active(self) -> int:
        return len(self._slot_req)

    @property
    def pending(self) -> int:
        return len(self._pending)

    def request(self, rid: int) -> Optional[Request]:
        """The live ``Request`` record for ``rid`` — queued, active, or
        finished-but-unpopped; None once popped or failed. Read-only view
        for fronting layers (the gateway polls ``.tokens`` off it for
        streaming delivery)."""
        for req in self._pending:
            if req.rid == rid:
                return req
        for req in self._slot_req.values():
            if req.rid == rid:
                return req
        return self._finished.get(rid)

    def failure(self, rid: int) -> Optional[Exception]:
        """The stored typed failure for ``rid`` (``DeadlineExceeded``,
        …) without raising/popping it; None while healthy."""
        return self._failed.get(rid)

    def abort(self, rid: int) -> bool:
        """Withdraw a LIVE request without recording a failure — the
        caller re-owns it (the gateway's drain-requeue path moves the
        request to a survivor and resumes token-exact from
        ``prompt ⧺ delivered``). Pending requests leave the queue;
        active ones release their slot (and cache rows), same mechanics
        as deadline expiry. Returns True when something was withdrawn;
        False for an unknown rid or a terminal request (finished results
        stay poppable, failures stay raised by ``pop_result``)."""
        with self._intake:
            for req in list(self._pending):
                if req.rid == rid:
                    self._pending.remove(req)
                    return True
        for slot, req in list(self._slot_req.items()):
            if req.rid == rid:
                del self._slot_req[slot]
                self._release_slot(slot)
                req.slot = None
                return True
        return False


class ContinuousBatcher(_BatcherBase):
    """Continuous batcher over a causal LM's dense KV cache.

    model: a GPT2ForCausalLM or LlamaForCausalLM (eval mode — any model
    exposing prefill/decode_step with the [B, 1] t convention). max_batch: slot count (ONE
    compiled decode executable serves every step at this batch). s_max:
    per-slot cache rows (prompt + generation must fit). eos_id: optional
    early-stop token. compile: jit.to_static the decode step (recommended;
    disable for debugging).
    """

    _engine = "dense"

    def __init__(self, model, max_batch: int = 8, s_max: int = 256,
                 eos_id: Optional[int] = None, compile: bool = True,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: Optional[float] = None,
                 seed: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 prompt_buckets="pow2"):
        import paddle_tpu as paddle

        self.model = model
        self._do_sample = do_sample
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        self._rng = np.random.RandomState(seed)
        self.max_batch = max_batch
        self.s_max = s_max
        self.eos_id = eos_id
        cfg = model.config
        self._check_window(cfg, s_max)
        L, d = cfg.num_hidden_layers, cfg.head_dim
        # GQA models cache at kv-head count (unexpanded)
        kvh = getattr(cfg, "num_key_value_heads", None) \
            or cfg.num_attention_heads
        self._caches = paddle.zeros([L, 2, max_batch, kvh, s_max, d],
                                    dtype=cfg.dtype)
        self._t = np.full((max_batch, 1), s_max - 1, np.int32)  # parked
        self._free = list(range(max_batch))
        self._init_queues(max_queue_depth=max_queue_depth,
                          default_deadline_s=default_deadline_s)
        self._last_tok = np.zeros((max_batch, 1), np.int64)
        # Admission pads prompts up this ladder (perf.buckets spec; None
        # disables): O(#buckets) prefill signatures instead of one per
        # distinct prompt length. Capped at s_max so the top rung is
        # always admissible.
        from ..perf.buckets import resolve_ladder
        self._prompt_ladder = resolve_ladder(prompt_buckets, hi=s_max)
        if compile:
            from .. import jit
            # donate the caches argument (tensor arg index 1): XLA reuses
            # the cache HBM in place instead of double-buffering per step
            self._step_fn = jit.to_static(model.decode_step,
                                          donate_args=(1,))
            self._prefill_fn = jit.to_static(model.prefill)
            # opprof observatory identities for the serving executables
            # (only meaningful on the compiled path)
            self._step_fn._opprof_label = "serving.decode"
            self._prefill_fn._opprof_label = "serving.prefill"
        else:
            self._step_fn = model.decode_step
            self._prefill_fn = model.prefill

    # -- request lifecycle --------------------------------------------------
    def _release_slot(self, slot: int):
        self._free.append(slot)
        self._t[slot, 0] = self.s_max - 1  # park

    def _admit(self) -> List[int]:
        """Move pending requests into free slots (prefill writes the slot's
        cache rows). Prompts are right-padded up the shared bucket ladder
        (``prompt_buckets``), so steady state runs O(#buckets) prefill
        signatures instead of one per distinct prompt length; the model
        gathers the true last-token logits at ``n_valid - 1``. Padded
        tokens are counted in ``serving.bucket_pad_waste``. Returns rids
        that finished AT admission (max_new_tokens == 1 or EOS on the
        prefill token)."""
        import paddle_tpu as paddle
        finished = []
        while True:
            with self._intake:
                if not (self._pending and self._free):
                    break
                req = self._pending.pop(0)
            slot = self._free.pop(0)
            self._trace_admit_begin(req)
            prompt = req.prompt
            n = len(prompt)
            if self._prompt_ladder is not None:
                bucket = self._prompt_ladder.bucket(n)
                if bucket != n:
                    # labeled by resolved rung so telemetry_dump can
                    # attribute waste per bucket without re-deriving the
                    # ladder
                    from ..observability.metrics import get_registry
                    get_registry().counter(
                        "serving.bucket_pad_waste",
                        "pad tokens admission added to reach the prompt "
                        "bucket",
                        labelnames=("rung",)).labels(
                            rung=str(bucket)).inc(bucket - n)
                    prompt = np.concatenate(
                        [prompt, np.zeros(bucket - n, prompt.dtype)])
                n_valid = paddle.to_tensor(np.full((1, 1), n, np.int32))
            else:
                n_valid = None
            ids = paddle.to_tensor(prompt[None, :])
            self._trace_prefill_begin(req)
            with paddle.no_grad():
                if n_valid is not None:
                    # n_valid is passed even for exact-rung prompts so every
                    # admission in a bucket shares ONE prefill signature
                    logits, cache, _t = self._prefill_fn(
                        ids, self.s_max, n_valid)
                else:
                    logits, cache, _t = self._prefill_fn(ids, self.s_max)
            self._trace_prefill_end(req, prompt_tokens=n,
                                    padded_to=len(prompt))
            # write the slot: caches[:, :, slot] = cache[:, :, 0]
            self._caches[:, :, slot] = cache[:, :, 0]
            tok = int(self._pick(np.asarray(logits._data)[:, -1])[0])
            req.slot = slot
            req.tokens.append(tok)
            self._tele.on_admit()
            self._tele.on_token(req)
            self._slot_req[slot] = req
            self._t[slot, 0] = len(req.prompt)
            self._last_tok[slot, 0] = tok
            self._trace_admit_end(req, slot)
            if self._maybe_finish(req, tok):
                finished.append(req.rid)
        return finished

    # -- the engine ---------------------------------------------------------
    def _step_impl(self) -> List[int]:
        """Admit, decode one token for every active slot, evict finished.
        Returns the rids that finished during THIS call (including ones
        that finished at admission)."""
        import paddle_tpu as paddle
        finished = self._admit()
        self._tele.set_gauges(len(self._pending), len(self._slot_req))
        if not self._slot_req:
            return finished
        self._tele.on_step()
        self._tele.on_occupancy(len(self._slot_req))
        n_active = len(self._slot_req)
        t0 = _time.perf_counter()
        tok_t = paddle.to_tensor(self._last_tok)
        t_t = paddle.to_tensor(self._t)
        # serving is inference by construction: the batcher supplies the
        # no_grad scope its donating compiled step requires
        with paddle.no_grad():
            logits, self._caches, _ = self._step_fn(tok_t, self._caches,
                                                    t_t)
        next_tok = self._pick(np.asarray(logits._data)[:, -1])
        for slot, req in list(self._slot_req.items()):
            tok = int(next_tok[slot])
            self._t[slot, 0] += 1
            req.tokens.append(tok)
            self._tele.on_token(req)
            self._last_tok[slot, 0] = tok
            if self._maybe_finish(req, tok):
                finished.append(req.rid)
        self._tele.on_decode_time(_time.perf_counter() - t0,
                                  tokens=n_active)
        self._tele.set_gauges(len(self._pending), len(self._slot_req))
        return finished


class PagedContinuousBatcher(_BatcherBase):
    """Continuous batching over the PAGED (block) KV cache.

    Reference surface: the vLLM-style serving loop the reference builds
    around block_multihead_attention
    (incubate/nn/functional/block_multihead_attention.py:19) — cache
    memory is a pool of physical pages, a block table maps each live
    sequence's logical blocks onto pool rows, and the scheduler admits/
    preempts by moving pages, not tensors.

    TPU design: the pool `[n_pages+1, H, bs, D]` per layer and the block
    table `[max_batch, blocks_per_seq]` both have static shapes, so ONE
    compiled decode executable serves every step at every occupancy. The
    host owns the free list; parked slots point every logical block at a
    reserved SCRATCH page (pool row n_pages) with dec_len 0, so their
    garbage decode writes land in scratch and never touch a live page.

    policy:
      * ``"reserve"`` — admission reserves the worst-case page count
        (ceil((prompt+max_new)/bs)) up front; head-of-line blocks when
        the pool can't cover it. Deterministic, no preemption.
      * ``"ondemand"`` — admission reserves only the prompt's pages;
        growth allocates one page as a sequence crosses each block
        boundary. On pool exhaustion the most-recently admitted request
        is PREEMPTED: its pages return to the pool and it re-queues with
        prompt ⧺ generated-so-far, so a later re-prefill recomputes its
        state exactly (greedy decode reproduces the same continuation).
    """

    _engine = "paged"

    def __init__(self, model, max_batch: int = 8, s_max: int = 256,
                 block_size: int = 16, n_pages: Optional[int] = None,
                 eos_id: Optional[int] = None, compile: bool = True,
                 policy: str = "reserve",
                 prefill_chunk: Optional[int] = None,
                 cache_quant: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 tier_quant: Optional[str] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: Optional[float] = None,
                 seed: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 prefix_cache: bool = False,
                 host_kv_gib: Optional[float] = None,
                 disk_kv_dir: Optional[str] = None,
                 disk_kv_gib: Optional[float] = None,
                 promo_timeout_s: float = 5.0,
                 promo_slots: int = 2,
                 promo_chunk_blocks: Optional[int] = 4,
                 session_store=None,
                 prompt_buckets=None,
                 draft_model=None, draft_k: int = 4):
        import paddle_tpu as paddle

        if policy not in ("reserve", "ondemand"):
            raise ValueError(f"unknown policy {policy!r}")
        # a model whose cache is more than pages of K and V says so (see
        # ``SambaYForCausalLM.paged_serving_contract``, and
        # ``GlmDsaForCausalLM``'s): per-slot state beside the pool, and the
        # options it cannot honour
        contract = getattr(model, "paged_serving_contract", dict)()
        asked = dict(prefix_cache=prefix_cache, kv_quant=kv_quant,
                     cache_quant=cache_quant, tier_quant=tier_quant,
                     draft_model=draft_model, session_store=session_store,
                     host_kv_gib=host_kv_gib or float(_os.environ.get(
                         "PADDLE_KV_HOST_GIB", "0") or 0.0),
                     disk_kv_dir=disk_kv_dir)
        for option, why in contract.get("unsupported", {}).items():
            if asked.get(option):
                raise ValueError(
                    f"{option} is not supported for "
                    f"{type(model).__name__}: {why}")
        if promo_slots < 1:
            raise ValueError("promo_slots must be >= 1")
        if promo_chunk_blocks is not None and promo_chunk_blocks < 1:
            raise ValueError("promo_chunk_blocks must be >= 1 (or None "
                             "for one whole-tail chunk)")
        if prefix_cache and cache_quant:
            raise ValueError(
                "prefix_cache shares pages across requests; dynamic "
                "cachekv quant scales are per-request, so a shared page "
                "would replay with the wrong scales — use static "
                "calibration or disable one")
        if draft_model is not None:
            if do_sample:
                raise ValueError("speculative decoding is greedy-only "
                                 "(draft_model requires do_sample=False)")
            if cache_quant:
                raise ValueError("draft_model is not supported with "
                                 "dynamic cachekv quant")
            if prefill_chunk:
                raise ValueError("draft_model is not supported with "
                                 "prefill_chunk (the draft pool would "
                                 "need its own chunk executables)")
            if draft_k < 1:
                raise ValueError("draft_k must be >= 1")
            if draft_model.config.vocab_size != model.config.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.config.vocab_size} != "
                    f"target vocab {model.config.vocab_size}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if cache_quant not in (None, "dynamic_int8"):
            raise ValueError(f"unknown cache_quant {cache_quant!r} "
                             f"(use None or 'dynamic_int8'; static int8 "
                             f"comes from model.calibrate_cachekv_int8)")
        if kv_quant not in (None, "int8"):
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             f"(use None or 'int8')")
        if kv_quant:
            # the explicit contract layer over the static-calibration
            # path: pages store int8 + the model's calibrated per-head
            # scales, dequantized inline at attention time (XLA fuses
            # the dequant into the matmul)
            if cache_quant:
                raise ValueError(
                    "kv_quant='int8' (static calibrated pages) and "
                    "cache_quant (dynamic per-request scales) are two "
                    "quantizers for the same pool; pick one")
            if getattr(model, "_cachekv_scales", None) is None:
                raise ValueError(
                    "kv_quant='int8' needs static per-head cache scales: "
                    "run model.calibrate_cachekv_int8(sample_ids) before "
                    "constructing the batcher")
            if draft_model is not None:
                raise ValueError(
                    "kv_quant is not supported with draft_model (the "
                    "draft pool would need its own calibration pass)")
        if tier_quant not in (None, "int8"):
            raise ValueError(f"unknown tier_quant {tier_quant!r} "
                             f"(use None or 'int8')")
        if tier_quant:
            if not prefix_cache:
                raise ValueError(
                    "tier_quant quantizes demoted host/disk tier blobs — "
                    "it needs prefix_cache=True (with a host tier)")
            if getattr(model, "_cachekv_scales", None) is not None:
                raise ValueError(
                    "tier_quant is redundant with calibrated int8 pages: "
                    "an int8 pool already spills int8 blobs natively "
                    "(and re-quantizing int8 codes would lose bits)")
        if cache_quant and prefill_chunk == 1:
            # a 1-token first chunk is decode-shaped (enc == 0,
            # this == 1): the op's scale opt-in guard rejects it, so fail
            # at construction instead of at first admission
            raise ValueError("cache_quant='dynamic_int8' needs "
                             "prefill_chunk >= 2 (a 1-token chunk is "
                             "indistinguishable from a decode step)")
        if prefill_chunk is not None and prefill_chunk > s_max:
            raise ValueError(f"prefill_chunk={prefill_chunk} exceeds "
                             f"s_max={s_max}")
        cfg = model.config
        self._check_window(cfg, s_max)
        self.model = model
        self.max_batch = max_batch
        self.s_max = s_max
        self.block_size = block_size
        self.blocks_per_seq = -(-s_max // block_size)
        # a model whose layers do not all keep every row names its page
        # groups; ``n_pages`` then gives a page count for each
        # (``_init_page_groups``); here it is the count of the group that
        # keeps every row, which the block table and the prefix cache name
        group_pages, n_pages = self._split_group_pages(contract, n_pages)
        if n_pages is None:
            n_pages = max_batch * self.blocks_per_seq
        self.n_pages = n_pages
        self.eos_id = eos_id
        self.policy = policy
        self._do_sample = do_sample
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        self._rng = np.random.RandomState(seed)

        self._scratch = n_pages                     # reserved pool row
        self._free_pages = list(range(n_pages))
        self._bt = np.full((max_batch, self.blocks_per_seq), self._scratch,
                           np.int32)
        self._dec = np.zeros((max_batch,), np.int32)
        self._free_slots = list(range(max_batch))
        self._init_queues(max_queue_depth=max_queue_depth,
                          default_deadline_s=default_deadline_s)
        self._admit_order: List[int] = []           # slots, oldest first
        self._last_tok = np.zeros((max_batch,), np.int64)

        # cross-request radix prefix reuse (SGLang RadixAttention shape):
        # admission matches the longest cached FULL-block prefix, points
        # the slot's block-table front at the cached pages, and prefills
        # only the suffix; the tree pins pages under live slots and
        # LRU-evicts unpinned chains back into the free list on pressure
        self.prefix_cache = None
        self._slot_nodes: Dict[int, list] = {}
        # tiered KV: one in-flight promotion STREAM (FIFO head only — the
        # batcher is single-threaded, so only the head request can wait),
        # pipelined as a bounded multi-chunk queue through the async
        # device_put worker: up to ``promo_slots`` chunks of
        # ``promo_chunk_blocks`` blocks are in flight at once, completed
        # chunks install in order at step boundaries while later chunks
        # (and decode) keep running. ``_promo_denied`` is an rid denylist
        # for requests whose promotion already failed (they fall back to
        # full prefill, never retry).
        self._promo = None
        self._promo_denied: set = set()
        self._promoter = None
        self.promo_timeout_s = promo_timeout_s
        self.promo_slots = promo_slots
        self.promo_chunk_blocks = promo_chunk_blocks
        self._demoted_seen = 0      # cache.demoted_bytes already countered
        # durable sessions: session id -> session-pinned node chain (spin
        # refs survive demotion; see prefix_cache.session_pin) and the
        # shared manifest store that makes a pause resumable on ANY
        # replica
        from .session_store import SessionStore
        self.session_store = (SessionStore(session_store)
                              if isinstance(session_store, str)
                              else session_store)
        self._session_pins: Dict[str, list] = {}
        if prefix_cache:
            from .prefix_cache import RadixPrefixCache, HostTier, DiskTier
            host_gib = (host_kv_gib if host_kv_gib is not None else
                        float(_os.environ.get("PADDLE_KV_HOST_GIB", "0")
                              or 0.0))
            host_tier = None
            if host_gib > 0:
                ddir = disk_kv_dir or _os.environ.get("PADDLE_KV_DISK_DIR")
                nxt = None
                if ddir:
                    dgib = (disk_kv_gib if disk_kv_gib is not None else
                            float(_os.environ.get("PADDLE_KV_DISK_GIB",
                                                  "16") or 16.0))
                    nxt = DiskTier(ddir, int(dgib * (1 << 30)))
                host_tier = HostTier(int(host_gib * (1 << 30)),
                                     next_tier=nxt)
            self.prefix_cache = RadixPrefixCache(
                block_size, host_tier=host_tier,
                spill=self._read_page_blob if host_tier is not None
                else None)
            if host_tier is not None:
                from ..perf.prefetch import AsyncLoader
                self._promoter = AsyncLoader(
                    depth=max(2, promo_slots),
                    name="paddle_tpu_kv_promoter",
                    workers=max(1, promo_slots))
        # optional admission ladder: the suffix prefill pads up shared
        # rungs (O(#buckets) prefill signatures, same lever as the dense
        # batcher's prompt_buckets); None keeps exact-length prefill
        from ..perf.buckets import resolve_ladder
        self._prompt_ladder = resolve_ladder(prompt_buckets, hi=s_max)
        from ..observability.metrics import get_registry as _get_reg
        _reg = _get_reg()
        self._prefix_hit_c = _reg.counter(
            "serving.prefix_hit_tokens",
            "prompt tokens served from the radix prefix cache")
        self._prefix_miss_c = _reg.counter(
            "serving.prefix_miss_tokens",
            "prompt tokens actually prefilled (no cached prefix)")
        self._prefix_evict_c = _reg.counter(
            "serving.prefix_evictions",
            "prefix-cache pages LRU-evicted under page pressure")
        self._pages_leaked_g = _reg.gauge(
            "serving.pages_leaked",
            "pages unaccounted for by free-list + block tables + prefix "
            "cache (an OOM-much-later bug if ever nonzero)")
        self._tier_hit_c = _reg.counter(
            "serving.prefix_tier_hit_tokens",
            "cached prompt tokens served, by the tier they were resident "
            "in at match time", labelnames=("tier",))
        self._promote_h = _reg.histogram(
            "serving.prefix_promotion_seconds",
            "host->device prefix promotion latency (submit to install)")
        self._promo_c = _reg.counter(
            "serving.prefix_promotions",
            "prefix pages promoted host/disk -> device")
        self._promo_fail_c = _reg.counter(
            "serving.prefix_promotion_failures",
            "promotions that failed/timed out/lost the page race "
            "(admission degraded to full prefill)")
        picks = _reg.counter(
            "serving.picks_total",
            "tokens chosen on the plain path (admission's first and every "
            "decode step's), by where: inside the executable, or on the "
            "host from fetched logits", labelnames=("where",))
        self._picks_c = {w: picks.labels(where=w)
                         for w in ("device", "host")}
        self._fetch_bytes_c = _reg.counter(
            "serving.fetch_bytes_total",
            "bytes copied to the host for token selection on the plain "
            "path: int32 ids or whole logits")
        self._demote_bytes_c = _reg.counter(
            "serving.prefix_demoted_bytes",
            "KV bytes spilled device -> host tier on eviction")
        self._host_bytes_g = _reg.gauge(
            "serving.kv_host_bytes",
            "bytes currently held by the host KV tier")
        self._kv_quant_g = _reg.gauge(
            "serving.kv_quant_enabled",
            "1 when the paged KV pool stores int8 pages (static "
            "calibrated scales), else 0")
        self._kv_quant_saved_g = _reg.gauge(
            "serving.kv_quant_bytes_saved",
            "pool bytes saved by int8 KV pages vs the model fp dtype")
        self._spill_raw_c = _reg.counter(
            "serving.prefix_spill_raw_bytes",
            "pre-quantization KV bytes demoted to the host tier "
            "(what the spill WOULD cost stored raw)")
        self._spill_blob_c = _reg.counter(
            "serving.prefix_spill_blob_bytes",
            "as-stored KV bytes demoted to the host tier (int8+scales "
            "when tier_quant is on; equals raw otherwise)")
        self._dequant_h = _reg.histogram(
            "quant.dequant_seconds",
            "main-thread blob dequantize time when installing promoted "
            "tier chunks (the overhead tier_quant pays on promotion)")

        self.cache_quant = cache_quant
        self.kv_quant = kv_quant
        self.tier_quant = tier_quant
        self._slot_state = bool(contract.get("slot_state"))
        self._position_axes = int(contract.get("position_axes", 0))
        self._init_page_groups(contract, group_pages, prefill_chunk)
        self._init_state_snapshots(contract)
        pool_pages = n_pages + 1                    # the scratch page too
        if self._primary_group:
            pool_pages = dict({self._primary_group: pool_pages}, **{
                g.name: g.n_pages + 1 for g in self._groups.values()})
        pool = model.paged_alloc(
            pool_pages, block_size,
            cache_dtype="int8" if cache_quant else None,
            **({"max_batch": max_batch} if self._slot_state else {}),
            **({"n_snapshots": self._snapshots.n} if self._snapshots
               else {}))
        if self._groups:
            self._init_group_bytes()
        self._init_slot_state_series(contract, pool)
        self._init_step_counts_series(contract)
        # paged_alloc auto-allocates int8 pages whenever the model
        # carries calibrated static scales — kv_quant='int8' is the
        # explicit contract (validated above), but the gauge reflects
        # the pool as actually allocated either way
        pool_int8 = bool(cache_quant) or (
            getattr(model, "_cachekv_scales", None) is not None)
        self._kv_quant_g.set(1 if pool_int8 else 0)
        if pool_int8:
            elems = sum(int(np.prod(kc.shape)) + int(np.prod(vc.shape))
                        for kc, vc in pool)
            try:
                fp_itemsize = np.dtype(
                    getattr(cfg, "dtype", "float32") or "float32").itemsize
            except TypeError:   # bfloat16-family names numpy can't parse
                fp_itemsize = 2
            self._kv_quant_saved_g.set(elems * max(0, fp_itemsize - 1))
        self._state = {
            "layers": pool,
            "block_tables": paddle.to_tensor(self._bt),
            "dec_lens": paddle.to_tensor(self._dec),
            "block_size": block_size,
            "capacity": self.blocks_per_seq * block_size,
            "zeros_b": paddle.to_tensor(np.zeros((max_batch,), np.int32)),
            "ones_b": paddle.to_tensor(np.ones((max_batch,), np.int32)),
            "cu_b": paddle.to_tensor(np.arange(max_batch + 1,
                                               dtype=np.int32)),
        }
        if self._groups:
            self._state["group_tables"] = self._group_tables()
        # the route the decode step's attention takes over this pool
        # ("kernel": the Pallas paged kernel; "gather": the XLA gather),
        # as the model that builds the executable decides it; every launch
        # of that executable is counted under it, and under how the step
        # writes its K/V rows ("page": whole pages along the pool's first
        # axis; "row": the row scatter)
        self._decode_path, self._decode_launch_c = \
            self._decode_attention_series(model, pool)
        self._kv_writer, self._kv_write_c = self._kv_write_series(model,
                                                                  pool)
        if cache_quant:
            # per-(slot, kv-head) dynamic scales, host-owned like the
            # block table; each sequence's prefill fills its slot row
            cfg = model.config
            kvh = getattr(cfg, "num_key_value_heads", None) \
                or cfg.num_attention_heads
            self._scales_np = [
                {k: np.ones((max_batch, kvh), np.float32)
                 for k in ("kq", "vq", "kdq", "vdq")}
                for _ in range(cfg.num_hidden_layers)]
            self._state["cache_scales"] = None  # filled by _sync_tables
            self._scales_dirty = True

        # in-batcher speculative decoding (the _speculative_loop recipe,
        # batched): the DRAFT pool mirrors the target pool's geometry and
        # SHARES self._bt, so one block table names both models' pages.
        # Per round: batched draft catch-up append (ends at each slot's
        # pending token -> proposal 1), k-1 draft decode steps, then ONE
        # target verify pass scoring pending + all k proposals; accept
        # the longest matching prefix + the target's correction. Output
        # is the target's greedy sequence token for token — the draft
        # only ever changes HOW MANY tokens a dispatch yields.
        self.draft_model = draft_model
        self.draft_k = draft_k
        self.spec_stats = {"rounds": 0, "proposed": 0, "matched": 0,
                           "fallback_steps": 0}
        if draft_model is not None:
            self._check_window(draft_model.config, s_max)
            dpool = draft_model.paged_alloc(n_pages + 1, block_size)
            self._ddec = np.zeros((max_batch,), np.int32)
            _, self._draft_launch_c = self._decode_attention_series(
                draft_model, dpool)
            self._dstate = {
                "layers": dpool,
                "block_tables": paddle.to_tensor(self._bt),
                "dec_lens": paddle.to_tensor(self._ddec),
                "block_size": block_size,
                "capacity": self.blocks_per_seq * block_size,
                "zeros_b": self._state["zeros_b"],
                "ones_b": self._state["ones_b"],
                "cu_b": self._state["cu_b"],
            }

            def _verify_body(ids, layers, bt, dec):
                return model.paged_prefill_into(
                    ids, layers, bt, block_size, dec_base=dec,
                    logits_all=True)

            def _catchup_body(ids, layers, bt, dec, at):
                return draft_model.paged_prefill_into(
                    ids, layers, bt, block_size, dec_base=dec,
                    logits_at=at)
            if compile:
                from .. import jit
                self._dstep_fn = jit.to_static(
                    draft_model.paged_decode_step, donate_args=(1,))
                self._verify_fn = jit.to_static(_verify_body,
                                                donate_args=(1,))
                self._catchup_fn = jit.to_static(_catchup_body,
                                                 donate_args=(1,))
                self._dstep_fn._opprof_label = "serving.draft_decode"
                self._verify_fn._opprof_label = "serving.verify"
                self._catchup_fn._opprof_label = "serving.catchup"
            else:
                self._dstep_fn = draft_model.paged_decode_step
                self._verify_fn = _verify_body
                self._catchup_fn = _catchup_body
            # catch-up width varies per round (1-2 steady state, wide
            # after fallback rounds); pad it up a pow2 ladder so the
            # catch-up executable count stays O(log s_max)
            from ..perf.buckets import BucketLadder
            self._cu_ladder = BucketLadder.pow2(hi=s_max)
        self.prefill_chunk = prefill_chunk

        # where the next token is chosen. Greedy: inside the executable
        # that made the logits, as one more output; the host fetches int32
        # ids ([B] a decode step, [1] a chunk; jnp.argmax keeps the lower
        # index of equal maxima, as numpy's does) and the [B, V] logits
        # never leave the device. Sampled: the generator's key is a
        # constant of a traced step, so the logits go to the host whole
        # and ``_select_token`` draws there
        def _chosen(logits):
            if do_sample:
                return logits
            return paddle.argmax(logits, axis=-1, dtype="int32")

        def _decode(tok, state):
            logits, state = model.paged_decode_step(tok, state)
            return _chosen(logits), state
        if compile:
            from .. import jit
            # donate the state pytree (arg 1): the page pool is the big
            # buffer — XLA appends into it in place every step. Compiled,
            # the step stays the model's own method and the choice is made
            # of its result (``_post``): a wrapper's frame under the model's
            # call cost 4 s of warm-up at 16 layers (PERF.md section 6)
            self._step_fn = jit.to_static(model.paged_decode_step,
                                          donate_args=(1,))
            self._step_fn._opprof_label = "serving.paged_decode"
            self._step_fn._post = lambda out: (_chosen(out[0]), out[1])
        else:
            self._step_fn = _decode
        if prefill_chunk is not None:
            # one fixed-width append executable serves EVERY prompt
            # length (vLLM chunked prefill); without it each distinct
            # prompt length costs a fresh prefill compile
            # ``slot_args``: a model with per-slot state is told its slot
            # and how many of the chunk's rows are real (``_slot_args``);
            # the pad rows of a fixed-width chunk must leave the state alone
            def _chunk(ids, layers, bt_row, dec, at, **slot_args):
                logits, layers = model.paged_prefill_into(
                    ids, layers, bt_row, block_size, dec_base=dec,
                    logits_at=at, **slot_args)
                return _chosen(logits), layers
            if compile:
                from .. import jit
                # donate the pool (arg 1) exactly like the decode step —
                # chunked prefill must not double-buffer the cache HBM
                self._chunk_fn = jit.to_static(_chunk, donate_args=(1,))
                self._chunk_fn._opprof_label = "serving.paged_prefill_chunk"
            else:
                self._chunk_fn = _chunk
            if cache_quant:
                # dynamic cachekv-int8 x chunked prefill: TWO fixed-width
                # executables — the first chunk computes the sequence's
                # scales (pad tail masked out of the stats via nvalid)
                # and returns them; later chunks consume them, so every
                # row of the timeline quantizes with ONE consistent
                # scale set (VERDICT r3 #5; reference analog
                # block_multihead_attention.py's scales+chunk signature)
                def _chunk_dyn_first(ids, layers, bt_row, dec, at, nvalid):
                    logits, layers, scales = model.paged_prefill_into(
                        ids, layers, bt_row, block_size, dec_base=dec,
                        logits_at=at, dynamic_cache_scales=True,
                        dynamic_scale_valid=nvalid)
                    return _chosen(logits), layers, scales

                def _chunk_dyn_rest(ids, layers, bt_row, dec, at, scales):
                    logits, layers = model.paged_prefill_into(
                        ids, layers, bt_row, block_size, dec_base=dec,
                        logits_at=at, cache_scales=scales)
                    return _chosen(logits), layers
                if compile:
                    self._chunk_dyn_first_fn = jit.to_static(
                        _chunk_dyn_first, donate_args=(1,))
                    self._chunk_dyn_rest_fn = jit.to_static(
                        _chunk_dyn_rest, donate_args=(1,))
                    self._chunk_dyn_first_fn._opprof_label = \
                        "serving.prefill_chunk_scales"
                    self._chunk_dyn_rest_fn._opprof_label = \
                        "serving.prefill_chunk_quant"
                else:
                    self._chunk_dyn_first_fn = _chunk_dyn_first
                    self._chunk_dyn_rest_fn = _chunk_dyn_rest

    # -- page groups ----------------------------------------------------------
    @staticmethod
    def _split_group_pages(contract: dict, n_pages):
        """(page counts of the contract's groups or None, the page count of
        the group that keeps every row)."""
        groups = contract.get("page_groups")
        if not groups:
            if isinstance(n_pages, dict):
                raise ValueError("n_pages gives page counts by group, and "
                                 "the model's cache has no page groups")
            return None, n_pages
        whole = [g for g, spec in groups.items() if spec.get("rows") is None]
        if len(whole) != 1:
            raise ValueError("one page group keeps every row (the block "
                             f"table's), not {whole}")
        if not isinstance(n_pages, dict) or set(n_pages) != set(groups):
            raise ValueError(
                f"the model's cache is the page groups {sorted(groups)}: "
                f"n_pages gives a page count for each, not {n_pages!r}")
        return dict(n_pages), int(n_pages[whole[0]])

    def _init_page_groups(self, contract: dict, group_pages,
                          prefill_chunk):
        """The groups that keep a window of rows (``prefix_cache.
        PageGroup``), each with its pages, its tables and its series. A
        slot's ring holds the window, the rows one prefill call writes and
        the partial blocks at both ends; a sequence is admitted only while
        every running one and it could hold a ring each, so a running
        sequence never waits for a page of a window group. Without groups
        nothing here is on any path."""
        self._groups: Dict[str, "PageGroup"] = {}
        self._primary_group = None
        self._slot_path: Dict[int, list] = {}    # every node of a slot's
        #   blocks so far (``_slot_nodes`` holds those it pins)
        if not group_pages:
            return
        from ..observability.metrics import get_registry
        from .prefix_cache import PageGroup
        reg = get_registry()
        width = prefill_chunk or self.s_max
        for name, spec in contract["page_groups"].items():
            if spec.get("rows") is None:
                self._primary_group = name
                continue
            ring = min(self.blocks_per_seq,
                       self._pages_for(spec["rows"] + width) + 3)
            if ring > group_pages[name]:
                raise ValueError(
                    f"page group {name!r}: one sequence holds up to {ring} "
                    f"pages, the group has {group_pages[name]}")
            self._groups[name] = PageGroup(
                name, spec["rows"], group_pages[name], self.block_size,
                ring, self.max_batch)
        if self.prefix_cache is not None:
            self.prefix_cache.groups = self._groups
        names = [self._primary_group] + list(self._groups)
        held = reg.gauge(
            "serving.kv_pages_held",
            "pages of a page group that running sequences hold",
            labelnames=("group",))
        released = reg.counter(
            "serving.kv_pages_released_total",
            "pages of a page group that sequences let go (behind the "
            "window while they ran, or at their end)",
            labelnames=("group",))
        reclaimed = reg.counter(
            "serving.kv_pages_reclaimed_total",
            "released pages the prefix cache still held that were handed "
            "out anew", labelnames=("group",))
        self._group_series = {
            n: (held.labels(group=n), released.labels(group=n),
                reclaimed.labels(group=n)) for n in names}
        self._group_seen = {n: [0, 0] for n in self._groups}
        self._row_bytes_h = reg.histogram(
            "serving.kv_bytes_per_resident_row",
            "bytes of all groups' pages that running sequences hold, as "
            "allocated, over their resident rows, a decode step")

    def _init_cut_series(self):
        """The series of matches cut back: by a window group that has
        reclaimed pages of the window before the boundary, or for want of
        a recurrent-state snapshot at it."""
        from ..observability.metrics import get_registry
        reg = get_registry()
        cut = reg.counter(
            "serving.prefix_hits_cut_total",
            "prefix matches cut back to a boundary at which a window "
            "group still had the window's pages, or the recurrent layers "
            "their state's snapshot", labelnames=("why",))
        self._hits_cut_c = {
            why: cut.labels(why=why)
            for why in ("window_pages_reclaimed", "no_state_snapshot")}
        self._matches_c = reg.counter(
            "serving.prefix_matches_total",
            "admissions whose prompt matched cached blocks, before any "
            "cut")
        self._rows_cut_c = reg.counter(
            "serving.prefix_rows_cut_total",
            "matched rows that a cut handed back to be prefilled again")

    def _init_group_bytes(self):
        """Bytes a page of each group, all its layers, from what the model
        says it allocated (``serving.kv_cache_bytes{group}``)."""
        from ..observability.metrics import get_registry
        kv_bytes = get_registry().get("serving.kv_cache_bytes")
        self._page_bytes = {}
        for n in [self._primary_group] + list(self._groups):
            pages = (self.n_pages if n == self._primary_group
                     else self._groups[n].n_pages) + 1
            self._page_bytes[n] = (kv_bytes.labels(group=n).value
                                   if kv_bytes else 0) / pages

    def _group_tables(self, slot: Optional[int] = None) -> dict:
        """Every window group's table (one slot's row of it) for the
        device. A copy: on the CPU the device may read a numpy buffer in
        place, and the next chunk's release writes the table while the
        last chunk still runs."""
        import paddle_tpu as paddle
        rows = slice(None) if slot is None else slice(slot, slot + 1)
        return {g.name: paddle.to_tensor(g.table[rows].copy())
                for g in self._groups.values()}

    def _cut_to_groups(self, matched: list) -> list:
        """A prefix match, cut back to the longest boundary at which every
        window group still has the pages of the window before it and, where
        the model's layers carry a recurrent state, a snapshot of it is
        still held."""
        if not self._by_chunk or not matched:
            return matched
        self._matches_c.inc()
        owners = [(g, "window_pages_reclaimed")
                  for g in self._groups.values()]
        if self._snapshots:
            owners.append((self._snapshots, "no_state_snapshot"))
        m, cut_by = len(matched), None
        while True:
            at, why = min(((o.usable(matched[:m]), why)
                           for o, why in owners), key=lambda c: c[0])
            if at == m:
                break
            m, cut_by = at, cut_by or why
        if cut_by:
            self._hits_cut_c[cut_by].inc()
            self._rows_cut_c.inc((len(matched) - m) * self.block_size)
        return matched[:m]

    def _group_rows(self, slot: int, dec: int, upto_row: int):
        """Rows ``dec .. upto_row`` of the slot are about to be written:
        every window group hands back what lies behind the window and backs
        the new rows."""
        for g in self._groups.values():
            if not g.advance(slot, dec, upto_row):
                raise RuntimeError(
                    f"page group {g.name!r} exhausted: slot {slot} needs a "
                    f"page at row {dec} (n_pages={g.n_pages}, "
                    f"{len(self._slot_req)} running)")

    def _group_insert(self, slot: int, ids_np, done_rows: int):
        """The slot's full blocks up to ``done_rows`` enter the prefix
        cache as its chunks complete, so that a block is the tree's before
        it falls behind the window: its window-group page then stays with
        the node when the sequence lets it go."""
        if self.prefix_cache is None:
            return
        path = self._slot_path[slot]
        n_blocks = min(done_rows, len(ids_np)) // self.block_size
        if n_blocks <= len(path):
            return
        start = len(path)
        created = self.prefix_cache.insert(
            ids_np, self._bt[slot], start, n_blocks,
            after=path[-1] if path else None, walked=path)
        self._slot_nodes[slot].extend(created)
        for j in range(start, n_blocks):
            for g in self._groups.values():
                g.adopt(slot, j, path[j])
            if self._snapshots:
                self._snapshots.adopt(slot, j, path[j])
        if self._snapshots:
            self._count_snapshots()

    def _count_groups(self):
        """The groups' series, once a decode step."""
        running = list(self._slot_req)
        held = {self._primary_group: int(np.sum(
            self._bt[running] != self._scratch))}
        for name, g in self._groups.items():
            held[name] = int(np.sum(g.upto[running] - g.first[running]))
            seen = self._group_seen[name]
            _, released, reclaimed = self._group_series[name]
            released.inc(g.released_total - seen[0])
            reclaimed.inc(g.reclaimed_total - seen[1])
            seen[:] = g.released_total, g.reclaimed_total
        for name, pages in held.items():
            self._group_series[name][0].set(pages)
        rows = int(self._dec[running].sum())
        if rows:
            self._row_bytes_h.observe(sum(
                pages * self._page_bytes[n]
                for n, pages in held.items()) / rows)

    # -- snapshots of per-slot state, for the prefix cache ---------------------
    def _init_state_snapshots(self, contract: dict):
        """A model whose layers carry a recurrent state says every how many
        rows a prefill can leave a snapshot of it (``state_snapshots``);
        with the prefix cache on, the device holds one for every so many
        rows of the pool and ``prefix_cache.StateSnapshots`` owns them: a
        match is cut to the deepest boundary that still has its snapshot,
        the first prefill behind a hit starts from it, and every prefill
        writes those of the boundaries it passes. The blocks then enter the
        tree as the prefill calls complete (``_group_insert``), as a model
        with page groups has them. Without the contract's word nothing here
        is on any path."""
        spec = contract.get("state_snapshots")
        self._snapshots = None
        self._snapshot_rows = int(spec["rows"]) if spec else 0
        self._resume_from: Dict[int, int] = {}   # slot -> snapshot, once
        if spec and self.prefix_cache is not None:
            from .prefix_cache import StateSnapshots
            self._snapshots = StateSnapshots(
                "state", self._snapshot_rows,
                self.n_pages * self.block_size // self._snapshot_rows,
                self.block_size)
            self.prefix_cache.groups = dict(self._groups,
                                            state=self._snapshots)
            from ..observability.metrics import get_registry
            reg = get_registry()
            self._snapshot_series = (
                reg.counter("serving.state_snapshots_taken_total",
                            "recurrent-state snapshots prefills wrote"),
                reg.counter("serving.state_snapshots_restored_total",
                            "admissions that started from a snapshot"),
                reg.counter("serving.state_snapshots_reclaimed_total",
                            "snapshots taken from a node of the prefix "
                            "cache, the store being full"),
                reg.gauge("serving.state_snapshots_held",
                          "snapshots that nodes of the prefix cache hold"))
            self._snapshots_seen = [0, 0, 0]
        if self._by_chunk:
            self._init_cut_series()

    @property
    def _by_chunk(self) -> bool:
        """Whether a sequence's blocks enter the prefix cache as its
        prefill calls complete and a match may be cut back."""
        return bool(self._groups) or self._snapshots is not None

    def _snapshot_args(self, slot: int, n_valid: int, first_row: int,
                       rows: int) -> dict:
        """``snapshot_from`` and ``snapshot_to`` of one prefill call over
        rows ``first_row .. first_row + rows`` of ``slot``, ``n_valid`` of
        them real: the snapshot the admission resumes from (its first call
        alone) and one for each boundary among the real rows."""
        import paddle_tpu as paddle
        every = self._snapshot_rows
        src = self._resume_from.pop(slot, -1)
        to = np.full((-(-rows // every),), -1, np.int32)
        if self._snapshots:
            with _span("serving.state_snapshot"):
                first = first_row // every + 1
                for j in range(first, (first_row + n_valid) // every + 1):
                    to[j - first] = self._snapshots.take(
                        slot, j * self._snapshots.blocks - 1)
        return {"snapshot_from": paddle.to_tensor(np.array([src], np.int32)),
                "snapshot_to": paddle.to_tensor(to)}

    def _count_snapshots(self):
        o = self._snapshots
        now = [o.taken_total, o.restored_total, o.reclaimed_total]
        for series, new, seen in zip(self._snapshot_series, now,
                                     self._snapshots_seen):
            series.inc(new - seen)
        self._snapshots_seen = now
        self._snapshot_series[3].set(len(o.owned))

    # -- per-slot state beside the pool ---------------------------------------
    def _init_slot_state_series(self, contract: dict, pool):
        """Bytes of state a slot holds whatever its length (0 where the
        cache is pages alone), the rows and rings of the model's window
        layers, and the registry series that follow them. The host never
        touches that state: a chunk at row 0 starts it from zero inside the
        executable, a released or preempted slot's is abandoned."""
        import jax
        from ..core.tensor import Tensor
        from ..observability.metrics import get_registry
        leaves = jax.tree_util.tree_leaves(
            pool["slots"], is_leaf=lambda t: isinstance(t, Tensor)) \
            if self._slot_state else []
        total = sum(int(np.prod(t.shape)) * t._data.dtype.itemsize
                    for t in leaves)
        self._slot_state_bytes = total // self.max_batch
        self._window_rows = int(contract.get("window_rows", 0))
        self._window_rings = int(contract.get("window_rings", 0))
        reg = get_registry()
        reg.gauge("serving.recurrent_state_bytes",
                  "bytes of per-slot state (recurrent state, window rings) "
                  "allocated beside the page pool").set(total)
        self._state_reset_c = reg.counter(
            "serving.state_resets",
            "admissions that started a slot's state from zero")
        self._window_drop_c = reg.counter(
            "serving.window_rows_overwritten",
            "rows the window rings dropped past the window, all rings "
            "together")
        self._window_read_c = reg.counter(
            "serving.window_rows_read",
            "rows inside the window that decode steps read, one ring's")

    def _count_slot_state_admit(self, n_rows: int):
        if self._slot_state:
            self._state_reset_c.inc()
            self._window_drop_c.inc(
                max(0, n_rows - self._window_rows) * self._window_rings)

    def _count_slot_state_step(self):
        """A decode step writes row ``dec`` of every running slot."""
        if not self._window_rings:
            return
        dec = self._dec[list(self._slot_req)]
        self._window_read_c.inc(int(np.minimum(dec + 1,
                                               self._window_rows).sum()))
        self._window_drop_c.inc(int((dec >= self._window_rows).sum())
                                * self._window_rings)

    def _slot_args(self, slot: int, n_valid: int, first_row: int,
                   rows: int) -> dict:
        """What ``paged_prefill_into`` takes besides: its slot where the
        model keeps per-slot state, how many of the chunk's rows are real
        there and where the model counts what its steps did, the slot's
        row of every window group's table where it has page groups, and
        the positions of the chunk's ``rows`` rows from ``first_row`` on
        where the contract says positions have axes."""
        if not (self._slot_state or self._step_counts or self._groups
                or self._position_axes):
            return {}
        import paddle_tpu as paddle
        args = {"slot": paddle.to_tensor(np.array([slot], np.int32))} \
            if self._slot_state else {}
        args["n_valid"] = paddle.to_tensor(np.array([n_valid], np.int32))
        if self._snapshot_rows:
            args.update(self._snapshot_args(slot, n_valid, first_row, rows))
        if self._groups:
            args["group_tables"] = self._group_tables(slot)
        if self._position_axes:
            args["position_ids"] = self._positions(
                np.arange(first_row, first_row + rows, dtype=np.int32))
        return args

    def _positions(self, rows: np.ndarray):
        """``position_ids`` [axes, N] of rows that are text: a row's number
        on every axis (the contract's ``position_axes``: a model whose
        rotary position is more than one number takes it from here, chunk
        and decode step alike, and not from where the row lies)."""
        import paddle_tpu as paddle
        return paddle.to_tensor(np.tile(rows, (self._position_axes, 1)))

    # -- what a model's steps chose -------------------------------------------
    _STEP_COUNTS = ("local", "assigned", "touched", "fullest", "scored",
                    "selected")

    def _init_step_counts_series(self, contract: dict):
        """A model whose steps choose (the rows its attention reads, the
        experts a token goes to) keeps ``step_counts`` in its cache, int32
        [2, layers, 6]: a layer's assignments to experts held here, the
        assignments its router made, the held experts touched, the fullest
        one's tokens, the (query, row) pairs its indexer scored and those
        its attention read. [0] is the last decode step's; [1] all the
        chunks' so far (real rows alone; it wraps: ``_add_chunk_counts``).
        A family without an indexer (``mellum``) leaves the last two columns
        zero, one without experts would leave the first four. Both come with
        the step's logits, and these series follow them."""
        self._step_counts = bool(contract.get("step_counts"))
        if not self._step_counts:
            return
        from ..observability.metrics import get_registry
        reg = get_registry()
        self._chunk_counts_seen = 0
        self._chunk_counts_due = []     # admissions' copies not yet read

        def by_phase(name, text):
            c = reg.counter(name, text, labelnames=("phase",))
            return {ph: c.labels(phase=ph) for ph in ("decode", "prefill")}

        self._step_counts_c = {
            "scored": by_phase(
                "serving.dsa_rows_scored_total",
                "(query, row) pairs the indexer scored, all layers "
                "together"),
            "selected": by_phase(
                "serving.dsa_rows_selected_total",
                "(query, row) pairs attention read after selection, all "
                "layers together"),
            "assigned": by_phase(
                "serving.moe_assignments_total",
                "token-to-expert assignments the routers made, all expert "
                "layers together"),
            "local": by_phase(
                "serving.moe_assignments_local_total",
                "assignments to experts held here"),
        }
        self._experts_touched_c = reg.counter(
            "serving.moe_experts_touched_total",
            "held experts a decode step multiplied through, all expert "
            "layers together")
        self._experts_touched_prefill_c = reg.counter(
            "serving.moe_experts_touched_prefill_total",
            "held experts a prefill chunk multiplied through, all expert "
            "layers together")
        self._expert_fullest_h = reg.histogram(
            "serving.moe_expert_tokens_max",
            "tokens of the fullest held expert, a decode step and expert "
            "layer")

    def _chunk_counts_after_admission(self):
        """``step_counts[1]`` as an admission leaves it, set aside and its
        copy to the host started; the next decode step's fetch reads it
        (``_add_step_counts``), so an admission waits for nothing more
        than its token. The slice is an array of its own: the cache's is
        donated to the next executable."""
        if self._step_counts:
            chunks = self._state["layers"]["step_counts"]._data[1]
            chunks.copy_to_host_async()
            self._chunk_counts_due.append(chunks)

    def _add_chunk_counts(self, chunks: np.ndarray):
        """What the chunks chose since the last reading: ``step_counts[1]``
        is an int32 running sum on the device, read as every admission
        left it and as every decode step finds it, each difference taken
        modulo 2^32. That is exact while ONE admission adds under 2^32 to
        one layer's entry: its scored pairs, L (L + 1) / 2 of a cold
        prompt of L tokens, pass that at 92,681 tokens. (Read with the
        decode steps alone, four cold 49,152-token admissions inside one
        gateway step wrapped it.)"""
        chunks = chunks.astype(np.int64)
        new = (chunks - self._chunk_counts_seen) % (1 << 32)  # int32 wraps
        self._chunk_counts_seen = chunks
        of = dict(zip(self._STEP_COUNTS, new.sum(0)))
        for name, series in self._step_counts_c.items():
            series["prefill"].inc(int(of[name]))
        self._experts_touched_prefill_c.inc(
            int(new[:, self._STEP_COUNTS.index("touched")].sum()))

    def _add_step_counts(self, counts: np.ndarray):
        step = counts[0].astype(np.int64)
        for due in self._chunk_counts_due:      # in the admissions' order
            self._add_chunk_counts(np.asarray(due))
        self._chunk_counts_due = []
        self._add_chunk_counts(counts[1])
        of = dict(zip(self._STEP_COUNTS, step.sum(0)))
        for name, series in self._step_counts_c.items():
            series["decode"].inc(int(of[name]))
        of = dict(zip(self._STEP_COUNTS, step.T))
        self._experts_touched_c.inc(int(of["touched"].sum()))
        for fullest in of["fullest"][of["assigned"] > 0]:
            self._expert_fullest_h.observe(float(fullest))

    # -- page accounting ----------------------------------------------------
    # A block-table page backs ``block_size`` rows of whatever the model
    # keeps in its pool: every layer's K and V, or one layer's that others
    # read. Layers that hold a window or a recurrent state hold no pages.
    def _pages_for(self, n_rows: int) -> int:
        return -(-n_rows // self.block_size)

    def _alloc_pages(self, slot: int, upto_row: int) -> bool:
        """Grow the slot's block-table row so rows [0, upto_row) are
        backed. A dry free list LRU-evicts unpinned prefix-cache chains
        first (cached-but-idle pages are reclaimable capacity, not
        occupancy). Returns False (allocating nothing) if even that can't
        cover it."""
        row = self._bt[slot]
        need_blocks = self._pages_for(upto_row)
        have = int(np.sum(row != self._scratch))
        grow = need_blocks - have
        if grow <= 0:
            return True
        if grow > len(self._free_pages) and self.prefix_cache is not None:
            self._evict_cache_pages(grow - len(self._free_pages))
        if grow > len(self._free_pages):
            return False
        for b in range(have, need_blocks):
            row[b] = self._free_pages.pop()
        return True

    def _available_pages(self) -> int:
        """Pages an allocation could obtain right now: the free list plus
        whatever the prefix cache would surrender to eviction."""
        n = len(self._free_pages)
        if self.prefix_cache is not None:
            n += self.prefix_cache.evictable_pages()
        return n

    # -- tiered KV: demotion + async promotion ------------------------------
    def _evict_cache_pages(self, n: int) -> List[int]:
        """Reclaim up to n pages from the prefix cache (demoting to the
        host tier when one is attached), mirroring the demoted-byte
        delta into the counter."""
        freed = self.prefix_cache.evict(n)
        if freed:
            self._free_pages.extend(freed)
            self._prefix_evict_c.inc(len(freed))
        d = self.prefix_cache.demoted_bytes - self._demoted_seen
        if d:
            self._demote_bytes_c.inc(d)
            self._demoted_seen = self.prefix_cache.demoted_bytes
        return freed

    @staticmethod
    def _quant_page(arr):
        """Per-head symmetric int8 quantization of one KV page row
        [H, block, D]: returns (int8 codes, float32 dequant scale
        [H, 1, 1]). amax==0 heads keep scale 1.0 so all-zero padding
        round-trips exactly."""
        a = np.asarray(arr, np.float32)
        amax = np.abs(a).max(axis=(1, 2), keepdims=True)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
        return q, scale

    def _quant_rows(self, rows):
        """Quantize a list of per-layer (k_page, v_page) rows into the
        tier-blob twin lists (int8 pages, per-head scales)."""
        pages, scales = [], []
        for k, v in rows:
            k8, ks = self._quant_page(k)
            v8, vs = self._quant_page(v)
            pages.append((k8, v8))
            scales.append((ks, vs))
        return pages, scales

    @staticmethod
    def _dequant_rows(pages, scales):
        return [(k8.astype(np.float32) * ks, v8.astype(np.float32) * vs)
                for (k8, v8), (ks, vs) in zip(pages, scales)]

    def _read_page_blob(self, node):
        """The cache's spill callback: read one node's KV rows off the
        pool back to pinned host numpy (on the CPU proxy this is a plain
        copy; on TPU the same call is the D2H readback). The draft pool
        shares the block table, so its rows spill alongside — promotion
        must restore BOTH pools for the page to be reusable.

        With ``tier_quant='int8'`` the fp rows demote as int8 codes plus
        per-head scales (the ``q`` tag marks the blob; ``_install_chunk``
        dequantizes on promotion), roughly halving what a chain costs the
        host/disk byte budget. An int8 pool (static calibration) never
        takes this path — its pages spill int8 natively and reinstall
        verbatim."""
        from .prefix_cache import blob_nbytes
        page = int(node.page)
        rows = [(np.asarray(kc._data[page]).copy(),
                 np.asarray(vc._data[page]).copy())
                for kc, vc in self._state["layers"]]
        drows = None
        if self.draft_model is not None:
            drows = [(np.asarray(kc._data[page]).copy(),
                      np.asarray(vc._data[page]).copy())
                     for kc, vc in self._dstate["layers"]]
        raw = blob_nbytes(rows) + (blob_nbytes(drows) if drows else 0)
        if self.tier_quant:
            # the "ts"/"ds" scale keys ARE the quantized-blob tag (a
            # string marker would poison the promotion device_put — the
            # loader ships the whole pytree and every leaf must be a
            # JAX-typable array)
            pages, scales = self._quant_rows(rows)
            blob = {"t": pages, "ts": scales}
            if drows is not None:
                dpages, dscales = self._quant_rows(drows)
                blob["d"] = dpages
                blob["ds"] = dscales
        else:
            blob = {"t": rows}
            if drows is not None:
                blob["d"] = drows
        self._spill_raw_c.inc(raw)
        self._spill_blob_c.inc(blob_nbytes(blob))
        return blob

    def _submit_promo_chunk(self, promo) -> bool:
        """Move one waiting chunk into flight. Its blobs are read off
        their tier IN THE WORKER (a callable payload — the loader
        materializes it before the device_put), so a later chunk's
        host/disk reads overlap an earlier chunk's main-thread install
        and, on a real accelerator, the in-flight DMA. Safe because
        every stream node carries ``node.promo`` and a pin for the
        duration: the evictors skip it, so its tier blob cannot move
        under the worker. A read error fails the chunk's future and the
        poller cancels the stream; False here only means the submit
        itself failed (loader closed/draining)."""
        from .prefix_cache import blob_nbytes
        chunk = promo["waiting"].pop(0)
        nodes = list(chunk["nodes"])
        cache = self.prefix_cache

        def _read():
            blobs = [cache.node_blob(n) for n in nodes]
            # worker-side write, published to the main thread by the
            # future's Event — read only after done()
            chunk["nbytes"] = [blob_nbytes(b) for b in blobs]
            return blobs

        try:
            chunk["future"] = self._promoter.submit(_read)
        except Exception:
            promo["waiting"].insert(0, chunk)
            return False
        promo["chunks"].append(chunk)
        return True

    def _start_promotion(self, req, dev: list, tail: list) -> bool:
        """Open a pipelined promotion stream for the off-device tail of
        ``req``'s matched path. Pins the WHOLE path (device prefix too:
        eviction must not demote what the request is about to use) and
        reserves one target page per tail node up front, so a completed
        transfer always has somewhere to land. The tail is split into
        ``promo_chunk_blocks``-block chunks with up to ``promo_slots``
        in flight through the async worker at once; ``promo_slots=1``
        with ``promo_chunk_blocks=None`` reproduces the old serial
        single-slot behavior. False (nothing pinned, nothing reserved)
        if pages can't be found or chaos says no — the caller degrades
        to device-prefix-only prefill."""
        from ..resilience.chaos import fault_point
        try:
            fault_point("kv.host_promote")
        except Exception:
            self._promo_fail_c.inc()
            self.prefix_cache.promotion_failures += 1
            self._promo_denied.add(req.rid)
            return False
        pinned = dev + tail
        self.prefix_cache.pin(pinned)
        need = len(tail)
        if need > len(self._free_pages):
            self._evict_cache_pages(need - len(self._free_pages))
        if need > len(self._free_pages):
            self.prefix_cache.unpin(pinned)
            return False
        pages = [self._free_pages.pop() for _ in range(need)]
        csize = self.promo_chunk_blocks or len(tail)
        t0 = _time.perf_counter()
        promo = {"req": req, "pinned": pinned,
                 # nodes/pages below shrink as chunks install — they are
                 # the NOT-YET-INSTALLED remainder (audit + cancel view)
                 "nodes": list(tail), "pages": list(pages),
                 "chunks": [],    # in flight, FIFO
                 "waiting": [{"nodes": tail[i:i + csize],
                              "pages": pages[i:i + csize],
                              "src_tiers": [n.residency
                                            for n in tail[i:i + csize]]}
                             for i in range(0, len(tail), csize)],
                 "t0": t0, "deadline": t0 + self.promo_timeout_s,
                 "installed_rows": 0, "src_tiers": []}
        while promo["waiting"] and len(promo["chunks"]) < self.promo_slots:
            if not self._submit_promo_chunk(promo):
                for ch in promo["chunks"] + promo["waiting"]:
                    self._free_pages.extend(ch["pages"])
                self.prefix_cache.unpin(pinned)
                self._promo_fail_c.inc()
                self.prefix_cache.promotion_failures += 1
                self._promo_denied.add(req.rid)
                # in-flight chunks are orphaned to the worker; their
                # staged arrays are dropped on arrival (no install record)
                return False
        self._promo = promo
        for n in tail:
            n.promo = promo
        return True

    def _cancel_promotion(self, deny: bool):
        """Abandon the promotion stream: every NOT-yet-installed chunk's
        reserved pages go back to the pool, the path is unpinned. Chunks
        already installed stay — they are cache-owned device pages now
        (a partial promotion just deepens the device prefix). ``deny``
        marks it a FAILURE (timeout/error/lost the page race) — the
        request won't retry and full-prefills instead; deny=False is the
        benign head-changed path."""
        promo, self._promo = self._promo, None
        for n in promo["nodes"]:
            n.promo = None
        self.prefix_cache.unpin(promo["pinned"])
        self._free_pages.extend(promo["pages"])
        if deny:
            self._promo_fail_c.inc()
            self.prefix_cache.promotion_failures += 1
            self._promo_denied.add(promo["req"].rid)

    def _install_chunk(self, promo, chunk, staged):
        """Land one completed chunk's staged arrays in the pool and hand
        its pages to the cache. Main thread only: compiled decode steps
        donate and replace the pool arrays every step — a background
        thread could write into a donated buffer."""
        for node, page, blob, nb in zip(chunk["nodes"], chunk["pages"],
                                        staged, chunk["nbytes"]):
            if isinstance(blob, dict) and blob.get("ts") is not None:
                # tier_quant blob: decode int8+scale back to fp before
                # the pool scatter. Timed — this is the promotion-side
                # cost tier_quant pays, and the ledger prices it.
                tq0 = _time.perf_counter()
                blob = {"t": self._dequant_rows(blob["t"], blob["ts"]),
                        **({"d": self._dequant_rows(blob["d"], blob["ds"])}
                           if "d" in blob else {})}
                self._dequant_h.observe(_time.perf_counter() - tq0)
            for li, (k_s, v_s) in enumerate(blob["t"]):
                kc, vc = self._state["layers"][li]
                kc._data = kc._data.at[page].set(k_s)
                vc._data = vc._data.at[page].set(v_s)
            if self.draft_model is not None and "d" in blob:
                for li, (k_s, v_s) in enumerate(blob["d"]):
                    kc, vc = self._dstate["layers"][li]
                    kc._data = kc._data.at[page].set(k_s)
                    vc._data = vc._data.at[page].set(v_s)
            self.prefix_cache.promote_node(node, page, nb)
            node.promo = None
        promo["installed_rows"] += len(chunk["nodes"]) * self.block_size
        promo["src_tiers"].extend(chunk["src_tiers"])
        remaining = set(id(n) for n in chunk["nodes"])
        promo["nodes"] = [n for n in promo["nodes"]
                          if id(n) not in remaining]
        drop = set(chunk["pages"])
        promo["pages"] = [p for p in promo["pages"] if p not in drop]

    def _poll_promotion(self) -> str:
        """Advance the promotion stream: 'pending' while transfers run
        (decode steps keep going — that's the overlap), 'ok' once every
        chunk has installed at a step boundary, 'failed' on error/
        timeout (remaining reserved pages reclaimed; chunks already
        installed stay, deepening the device prefix). Each completed
        chunk refreshes the deadline — the timeout bounds PROGRESS, not
        total stream time, so a long cold resume isn't penalized for its
        length."""
        promo = self._promo
        while promo["chunks"]:
            head = promo["chunks"][0]
            fut = head["future"]
            if not fut.done():
                if _time.perf_counter() < promo["deadline"]:
                    return "pending"
                self._cancel_promotion(deny=True)
                return "failed"
            try:
                staged = fut.result()
            except Exception:
                self._cancel_promotion(deny=True)
                return "failed"
            self._install_chunk(promo, head, staged)
            promo["chunks"].pop(0)
            promo["deadline"] = _time.perf_counter() + self.promo_timeout_s
            while (promo["waiting"]
                   and len(promo["chunks"]) < self.promo_slots):
                if not self._submit_promo_chunk(promo):
                    self._cancel_promotion(deny=True)
                    return "failed"
        if promo["waiting"]:           # pragma: no cover — defensive
            self._cancel_promotion(deny=True)
            return "failed"
        self.prefix_cache.unpin(promo["pinned"])
        self._promote_h.observe(_time.perf_counter() - promo["t0"])
        self._promo_c.inc(promo["installed_rows"] // self.block_size)
        self._promo_installed_rows = promo["installed_rows"]
        self._promo_src_tiers = list(promo["src_tiers"])
        self._promo = None
        return "ok"

    def close(self):
        """Retire the async promotion worker (idempotent; the worker is
        a daemon thread, so skipping this only delays cleanup)."""
        if self._promoter is not None:
            self._promoter.close()

    def _release_slot(self, slot: int):
        """Reset the slot's block-table row to scratch, returning its pages
        to the free list — except those the prefix cache owns (the cache's
        refcounts, not this row, decide their lifetime)."""
        keep = ()
        if self.prefix_cache is not None:
            nodes = self._slot_nodes.pop(slot, None)
            if nodes:
                self.prefix_cache.unpin(nodes)
                keep = {n.page for n in nodes}
        row = self._bt[slot]
        for b in range(self.blocks_per_seq):
            if row[b] != self._scratch:
                if int(row[b]) not in keep:
                    self._free_pages.append(int(row[b]))
                row[b] = self._scratch
        self._dec[slot] = 0
        for g in self._groups.values():
            g.drop_slot(slot)
        if self._snapshots:
            self._snapshots.drop_slot(slot)
        self._resume_from.pop(slot, None)
        self._slot_path.pop(slot, None)
        if self.draft_model is not None:
            self._ddec[slot] = 0
        if self.cache_quant:
            for layer in self._scales_np:
                for k in layer:
                    layer[k][slot] = 1.0
            self._scales_dirty = True
        self._free_slots.append(slot)
        self._admit_order.remove(slot)
        self.audit_pages()

    def audit_pages(self) -> int:
        """Set-reconcile the page pool after every release: free list ∪
        block-table rows ∪ prefix-cache pages must cover range(n_pages)
        exactly once (block-table ∩ cache overlap is the POINT — shared
        prefixes — but free ∩ anything is a double-free). Publishes
        ``serving.pages_leaked`` and raises on any anomaly, so a leak
        fails the releasing operation instead of surfacing as OOM much
        later. Returns the leak count, all page groups together (always 0
        on the non-raising path)."""
        free_set = set(self._free_pages)
        used = set()
        for slot in range(self.max_batch):
            for b in self._bt[slot]:
                if b != self._scratch:
                    used.add(int(b))
        if self._promo is not None:
            # pages reserved for an in-flight promotion are spoken for
            used.update(int(p) for p in self._promo["pages"])
        cache_pages = set()
        if self.prefix_cache is not None:
            cp = self.prefix_cache.pages()
            cache_pages = set(cp)
            if len(cache_pages) != len(cp):
                raise RuntimeError("page accounting bug: prefix cache "
                                   "holds a page in two nodes")
        leaked = set(range(self.n_pages)) - free_set - used - cache_pages
        self._pages_leaked_g.set(len(leaked))
        if len(free_set) != len(self._free_pages):
            raise RuntimeError("page accounting bug: free list holds a "
                               "page twice")
        double = free_set & (used | cache_pages)
        if leaked or double:
            raise RuntimeError(
                f"page accounting bug: leaked={sorted(leaked)} "
                f"free-but-used={sorted(double)}")
        if self.prefix_cache is not None:
            # cross-tier half of the audit: host/disk blob byte
            # accounting must reconcile exactly too
            rep = self.prefix_cache.audit_tiers()
            self._host_bytes_g.set(rep.get("host_bytes", 0))
        # every window group reconciles its own pool the same way, and the
        # store of state snapshots its indices
        return sum(g.audit() for g in self._groups.values()) \
            + (self._snapshots.audit() if self._snapshots else 0)

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    # -- durable sessions ---------------------------------------------------
    def _session_gauge(self):
        if not hasattr(self, "_session_pin_g"):
            from ..observability.metrics import get_registry
            self._session_pin_g = get_registry().gauge(
                "session.pinned_blocks",
                "prefix-cache blocks currently held by session pins")
        return self._session_pin_g

    def model_identity(self) -> str:
        from .session_store import model_identity
        return model_identity(self.model)

    def pin_session(self, session_id: str, token_ids) -> int:
        """Session-pin the cached chain covering ``token_ids``' full
        blocks (replacing any previous pin for this id): churn may demote
        the chain to host/disk but can no longer drop it out of the last
        tier, so a resume finds it promotable. Local-only — durability
        across replicas is the manifest's job (``pause_session``).
        Returns the number of pinned blocks."""
        if self.prefix_cache is None:
            return 0
        self.unpin_session(session_id)
        path = self.prefix_cache.match(token_ids)
        if path:
            self.prefix_cache.session_pin(path)
            self._session_pins[session_id] = path
        self._session_gauge().set(
            sum(len(p) for p in self._session_pins.values()))
        from ..observability.fleet import spool_event
        spool_event("session", op="pin", session=session_id,
                    blocks=len(path))
        return len(path)

    def unpin_session(self, session_id: str) -> bool:
        nodes = self._session_pins.pop(session_id, None)
        if not nodes:
            return False
        self.prefix_cache.session_unpin(nodes)
        self._session_gauge().set(
            sum(len(p) for p in self._session_pins.values()))
        return True

    def release_sessions(self):
        """Drop every local session pin (manifests are untouched — the
        sessions stay resumable elsewhere). The close/remove path."""
        for sid in list(self._session_pins):
            self.unpin_session(sid)

    def pause_session(self, session_id: str, token_ids) -> bool:
        """Pause a conversation: pin its chain locally AND publish the
        crash-safe manifest (id -> chain hashes + tokens + model identity)
        to the shared store, so ANY replica can resume it later. True iff
        the manifest published atomically; on a torn publish (chaos, IO)
        the chain stays pinned locally — a same-replica resume still
        rides the cache, a cross-replica one falls back to re-prefill."""
        self.pin_session(session_id, token_ids)
        if self.session_store is None:
            return False
        from .session_store import SessionManifest
        toks = np.asarray(token_ids, np.int64).reshape(-1)
        m = SessionManifest(session_id=session_id,
                            token_ids=[int(t) for t in toks],
                            block_size=self.block_size,
                            model=self.model_identity())
        return self.session_store.publish(m)

    def resume_session(self, session_id: str):
        """Resolve a paused session to the token ids to resubmit
        (``prompt ⧺ generated`` of the paused turn — submitting them plus
        the new turn re-matches the pinned chain and streams the tiered
        promotion). ``None`` when the manifest is missing/torn/corrupt or
        the model identity changed (typed finding in the store; the
        caller full-prefills from its own context — token-exact either
        way)."""
        if self.session_store is None:
            return None
        m = self.session_store.load(session_id,
                                    expect_model=self.model_identity())
        if m is None:
            return None
        # a block_size mismatch only invalidates the manifest's chain
        # hashes (a routing hint); the tokens stay good — the radix tree
        # matches raw token blocks, so resume correctness is unaffected
        return np.asarray(m.token_ids, np.int64)

    # -- request lifecycle --------------------------------------------------
    def _validate(self, prompt: np.ndarray, max_new_tokens: int):
        super()._validate(prompt, max_new_tokens)
        worst = len(prompt) + max_new_tokens
        if self.prefill_chunk:
            # chunk padding can demand more rows than the timeline (a
            # preemption-resume prompt pads up to one chunk beyond);
            # reject now rather than livelock admission later
            worst = max(worst, min(
                -(-worst // self.prefill_chunk) * self.prefill_chunk,
                self.blocks_per_seq * self.block_size))
        elif self._prompt_ladder is not None:
            # same hazard as chunk padding: the ladder can round a
            # resume-length prompt past the timeline
            worst = max(worst, min(self._prompt_ladder.bucket(worst),
                                   self.blocks_per_seq * self.block_size))
        pages = self._pages_for(worst)
        if pages > self.n_pages:
            raise ValueError(f"request needs {pages} pages but the pool "
                             f"holds {self.n_pages}")

    def _admit(self) -> List[int]:
        """FIFO admission into free slots, gated by page availability
        (reserve: worst case up front; ondemand: prompt + first step).
        Head-of-line blocking is deliberate — it preserves arrival order
        the way the reference's serving queue does."""
        import paddle_tpu as paddle
        finished = []
        if self._promo is not None and (
                not self._pending
                or self._pending[0] is not self._promo["req"]):
            # the promotion's request left the head (expired, requeued):
            # benign cancel, pages back
            self._cancel_promotion(deny=False)
        while self._pending and self._free_slots:
            req = self._pending[0]
            # a preempted request resumes from prompt ⧺ generated; chunked
            # prefill pads to the chunk width (capacity-clamped)
            ids_full = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int64)]) \
                if req.tokens else req.prompt
            matched = []
            promoted_rows = 0
            src_tiers: List[str] = []
            if self.prefix_cache is not None:
                # cap at (L-1)//bs blocks: at least one suffix token must
                # prefill — the first generated token needs logits, and a
                # fully-cached prompt has none to offer
                cap_blocks = (len(ids_full) - 1) // self.block_size
                matched = self.prefix_cache.match(ids_full,
                                                  max_blocks=cap_blocks)
                dev, tail = self.prefix_cache.split_device(matched)
                if self._promo is not None:
                    st = self._poll_promotion()
                    if st == "pending":
                        if not self._slot_req:
                            # nothing to overlap with: don't hot-spin the
                            # step loop while the transfer lands
                            _time.sleep(500e-6)
                        break
                    # ok: the tail is device-resident now; failed: the
                    # tail stays off-device and is skipped below — either
                    # way re-split the fresh tree state
                    matched = self.prefix_cache.match(ids_full,
                                                      max_blocks=cap_blocks)
                    dev, tail = self.prefix_cache.split_device(matched)
                    matched = dev
                    if st == "ok":
                        promoted_rows = self._promo_installed_rows
                        src_tiers = self._promo_src_tiers
                elif (tail and self._promoter is not None
                        and req.rid not in self._promo_denied):
                    if self._start_promotion(req, dev, tail):
                        break     # decode steps continue while it flies
                    matched = dev
                else:
                    # off-device tail unusable (no promoter, or this
                    # request already burned its promotion): prefill it
                    # fresh — insert() upgrades the stale nodes in place
                    matched = dev
                matched = self._cut_to_groups(matched)
                if matched:
                    # pin BEFORE the availability gate: the gate may
                    # admit on the promise of evicting OTHER chains, and
                    # eviction must not be able to take these pages
                    self.prefix_cache.pin(matched)
            m_rows = len(matched) * self.block_size
            ids_np, L, padded_len, upto = self._admission_plan(req, m_rows)
            need = self._pages_for(upto) - len(matched)
            # the tree is walked for what it could give up only where the
            # free list alone does not cover the request
            short = need - len(self._free_pages)
            if short > 0 and short > (
                    self.prefix_cache.evictable_pages()
                    if self.prefix_cache is not None else 0) or any(
                    (len(self._slot_req) + 1) * g.ring > g.n_pages
                    for g in self._groups.values()):
                if matched:
                    self.prefix_cache.unpin(matched)
                break
            with _span("serving.admit", rid=req.rid,
                       prompt_tokens=len(ids_np), hit_tokens=m_rows,
                       state_bytes=self._slot_state_bytes, carried=0):
                with self._intake:
                    self._pending.pop(0)
                self._promo_denied.discard(req.rid)
                slot = self._free_slots.pop(0)
                if matched:
                    self._bt[slot, :len(matched)] = [n.page for n in matched]
                if not self._alloc_pages(slot, upto):
                    raise RuntimeError("page accounting bug: admission gate "
                                       "passed but allocation failed")
                if self._by_chunk:
                    for g in self._groups.values():
                        g.start(slot, matched)
                    self._slot_path[slot] = list(matched)
                    self._slot_nodes[slot] = list(matched)
                    if self._groups and not self.prefill_chunk:
                        self._group_rows(slot, m_rows, padded_len)
                if self._snapshots and matched:
                    with _span("serving.state_restore", rid=req.rid,
                               rows=m_rows):
                        self._resume_from[slot] = \
                            self._snapshots.resume(matched)
                self._trace_admit_begin(req)
                self._trace_prefill_begin(req)
                if slot not in self._resume_from:
                    self._count_slot_state_admit(L)
                bt_row = paddle.to_tensor(self._bt[slot:slot + 1])
                S = L - m_rows
                with paddle.no_grad():
                    if self.prefill_chunk:
                        logits = self._prefill_chunked(
                            ids_np[m_rows:], bt_row, slot, dec0=m_rows,
                            rid=req.rid, ids_full=ids_np)
                    elif self.cache_quant:
                        ids = paddle.to_tensor(ids_np[None, :])
                        logits, self._state["layers"], seq_scales = \
                            self.model.paged_prefill_into(
                                ids, self._state["layers"], bt_row,
                                self.block_size, dynamic_cache_scales=True)
                        self._store_slot_scales(slot, seq_scales)
                    elif m_rows or self._prompt_ladder is not None:
                        # suffix prefill: append S real tokens after the
                        # m_rows cached rows, padded up to the resolved rung
                        # (pad rows sit past the timeline — stale until
                        # decode overwrites them, never read before that)
                        pad_s = padded_len - m_rows
                        if pad_s != S:
                            self._count_pad_waste(pad_s, pad_s - S)
                        sfx = np.zeros((pad_s,), np.int64)
                        sfx[:S] = ids_np[m_rows:]
                        logits, self._state["layers"] = \
                            self.model.paged_prefill_into(
                                paddle.to_tensor(sfx[None, :]),
                                self._state["layers"], bt_row,
                                self.block_size,
                                dec_base=paddle.to_tensor(
                                    np.array([m_rows], np.int32)),
                                logits_at=paddle.to_tensor(
                                    np.array([S - 1], np.int32)),
                                **self._slot_args(slot, S, m_rows, pad_s))
                    else:
                        ids = paddle.to_tensor(ids_np[None, :])
                        logits, self._state["layers"] = \
                            self.model.paged_prefill_into(
                                ids, self._state["layers"], bt_row,
                                self.block_size,
                                **self._slot_args(slot, L, 0, L))
                    if self.draft_model is not None:
                        # mirror the suffix into the DRAFT pool (same block-
                        # table row, its own physical pages); cached pages
                        # already hold this prefix's draft rows — every page
                        # enters the tree through an admission that wrote
                        # both pools
                        dfx = np.zeros((max(S, 1),), np.int64)
                        dfx[:S] = ids_np[m_rows:]
                        _dl, self._dstate["layers"] = \
                            self.draft_model.paged_prefill_into(
                                paddle.to_tensor(dfx[None, :]),
                                self._dstate["layers"], bt_row,
                                self.block_size,
                                dec_base=paddle.to_tensor(
                                    np.array([m_rows], np.int32)),
                                logits_at=paddle.to_tensor(
                                    np.array([0], np.int32)))
                        self._ddec[slot] = L
                if self.prefix_cache is not None:
                    self._prefix_hit_c.inc(m_rows)
                    self._prefix_miss_c.inc(S)
                    self.prefix_cache.hit_tokens += m_rows
                    self.prefix_cache.miss_tokens += S
                    self._tier_hit_c.labels(tier="device").inc(
                        m_rows - promoted_rows)
                    for t in src_tiers:
                        self._tier_hit_c.labels(tier=t).inc(self.block_size)
                    self.prefix_cache.host_hit_tokens += promoted_rows
                    if self._by_chunk:
                        self._group_insert(slot, ids_np, L)
                    else:
                        new_nodes = self.prefix_cache.insert(
                            ids_np, self._bt[slot], len(matched),
                            L // self.block_size)
                        self._slot_nodes[slot] = list(matched) + new_nodes
                end_tags = dict(prompt_tokens=len(ids_np), pages=need,
                                prefix_hit=m_rows, padded_to=padded_len)
                if promoted_rows:
                    # the ledger splits evicted_prefix_recompute pricing on
                    # this: a promoted resume repaid its eviction from the
                    # host tier, not by recomputing
                    end_tags["host_promoted"] = promoted_rows
                self._trace_prefill_end(req, **end_tags)
                # chunked admission under greedy brings the one id its
                # last chunk chose; every other admission the [1, V] logits
                with _span("serving.fetch"):
                    fetched = np.asarray(logits._data)
                self._chunk_counts_after_admission()
                tok = int(self._pick(fetched)[0])
                self._count_picks(fetched, 1)
                req.slot = slot
                req.tokens.append(tok)
                self._tele.on_admit()
                self._tele.on_token(req)
                self._slot_req[slot] = req
                self._admit_order.append(slot)
                self._dec[slot] = len(ids_np)
                self._last_tok[slot] = tok
                self._trace_admit_end(req, slot)
                if self._maybe_finish(req, tok):
                    finished.append(req.rid)
        return finished

    def _pick(self, fetched):
        """Next tokens [B] from what a step's fetch brought. Ids (one
        axis, int32): the executable chose them on the device (greedy,
        the decode step and chunked admission) and they pass through.
        Logits [B, V]: picked here on the host, by the model's sampling
        with ``do_sample``, by numpy's argmax for a greedy admission that
        prefilled its whole prompt uncompiled."""
        if fetched.ndim == 1:
            return fetched
        return super()._pick(fetched)

    def _count_picks(self, fetched: np.ndarray, tokens: int):
        self._fetch_bytes_c.inc(fetched.nbytes)
        self._picks_c["device" if fetched.ndim == 1 else "host"].inc(tokens)

    def _count_pad_waste(self, rung: int, waste: int):
        from ..observability.metrics import get_registry
        get_registry().counter(
            "serving.bucket_pad_waste",
            "pad tokens admission added to reach the prompt bucket",
            labelnames=("rung",)).labels(rung=str(rung)).inc(waste)

    def _prefill_chunked(self, ids_np, bt_row, slot, dec0: int = 0,
                         rid: int = -1, ids_full=None):
        """Feed the prompt through fixed-width append chunks (ONE compiled
        executable for every prompt length). The tail chunk is zero-padded;
        pad rows land past the true timeline and are overwritten by decode
        before any bounded read reaches them. Returns what the chunk that
        holds the last REAL position handed back for selection: the id it
        chose [1] under greedy, that position's logits [1, V] with
        ``do_sample``.

        Dynamic cachekv-int8 composition (VERDICT r3 #5): with
        cache_quant set, chunk 1 computes the sequence's per-head scales
        from its VALID rows (the zero-pad tail is masked out of the amax
        statistics, matching what an unpadded single-call prefill would
        compute) and returns them; every later chunk — and decode —
        quantizes with those same scales, so the timeline is scale-
        consistent end to end. For prompts within the chunk width this is
        exactly the unchunked dynamic path, token-for-token; longer
        prompts derive their scales from the first chunk's rows, the same
        first-window semantics the reference's serving stack uses when
        scales must exist before the whole prompt has been seen.

        ``dec0``: cached-prefix offset — ``ids_np`` is the SUFFIX and the
        chunks append after ``dec0`` existing rows (prefix-cache hits;
        always 0 on the quantized path, which is gated off prefix reuse).
        ``rid`` tags each chunk's ``serving.prefill_chunk`` span.
        ``ids_full``: the whole prompt, cached prefix and all, where the
        model has page groups: before a chunk the window groups hand back
        what fell behind the window and back the chunk's rows
        (``serving.release_window``), after it the blocks completed enter
        the prefix cache.
        """
        import paddle_tpu as paddle
        C = self.prefill_chunk
        L = len(ids_np)
        cap = self.blocks_per_seq * self.block_size
        padded_len = min(-(-L // C) * C, cap - dec0)
        padded = np.zeros((padded_len,), np.int64)
        padded[:L] = ids_np
        dec = 0
        logits = None
        scales = None
        last_rest = None          # (dec, nvalid) of the last rest chunk
        first_nvalid = 0          # valid rows in the scale-setting chunk
        while dec < padded_len:
            w = min(C, padded_len - dec)     # tail shortens at capacity
            has_last = 0 <= (L - 1) - dec < w
            at = (L - 1) - dec if has_last else 0
            if self._groups:
                with _span("serving.release_window"):
                    self._group_rows(slot, dec0 + dec, dec0 + dec + w)
            with _span("serving.prefill_chunk", rid=rid,
                       state_bytes=self._slot_state_bytes,
                       carried=int(self._slot_state and dec0 + dec > 0)):
                ids_t = paddle.to_tensor(padded[None, dec:dec + w])
                dec_t = paddle.to_tensor(np.array([dec0 + dec], np.int32))
                at_t = paddle.to_tensor(np.array([at], np.int32))
                if not self.cache_quant:
                    lg, self._state["layers"] = self._chunk_fn(
                        ids_t, self._state["layers"], bt_row, dec_t, at_t,
                        **self._slot_args(slot, min(L - dec, w),
                                          dec0 + dec, w))
                elif scales is None:
                    first_nvalid = min(L - dec, w)
                    nvalid = paddle.to_tensor(
                        np.array([first_nvalid], np.int32))
                    lg, self._state["layers"], scales = \
                        self._chunk_dyn_first_fn(
                            ids_t, self._state["layers"], bt_row, dec_t,
                            at_t, nvalid)
                else:
                    lg, self._state["layers"] = self._chunk_dyn_rest_fn(
                        ids_t, self._state["layers"], bt_row, dec_t, at_t,
                        scales)
                    if L - dec > 0:
                        last_rest = (dec, min(L - dec, w))
            if has_last:
                # the final chunk always contains position L-1 (its start
                # k*C < L by the ceil-padding construction)
                logits = lg
            dec += w
            if self._by_chunk:
                self._group_insert(slot, ids_full, dec0 + dec)
        if scales is not None:
            if last_rest is not None:
                # sampled saturation telemetry: one baseline read of the
                # scale-setting chunk, one read of the final rest chunk
                base = self._topbin_counts(bt_row, 0, first_nvalid)
                self._record_chunk_saturation(
                    bt_row, last_rest[0], last_rest[1],
                    baseline=None if base is None
                    else base[0] / max(base[1], 1))
            self._store_slot_scales(slot, scales)
        return logits

    def _topbin_counts(self, bt_row, dec, nvalid):
        """(top_bin_entries, total_entries) over the int8 K/V rows at
        positions [dec, dec+nvalid) of this slot, or None if the pool is
        not quantized. |q| >= 127 is a PROXY: true saturation and
        legitimately-in-range values within ~0.4% of amax both land in
        the top bin, which is why the warning below is baseline-relative
        rather than absolute."""
        if nvalid <= 0:
            return None
        bt = np.asarray(getattr(bt_row, "_data", bt_row))[0]
        pos = np.arange(dec, dec + nvalid)
        phys = bt[pos // self.block_size]
        off = pos % self.block_size
        clipped = total = 0
        for kc, vc in self._state["layers"]:
            for pool in (kc, vc):
                arr = np.asarray(getattr(pool, "_data", pool)[phys, :, off])
                if arr.dtype != np.int8:
                    return None
                clipped += int((np.abs(arr.astype(np.int32)) >= 127).sum())
                total += arr.size
        return clipped, total

    def _record_chunk_saturation(self, bt_row, dec, nvalid,
                                 baseline=None):
        """First-window telemetry (ADVICE r4, serving.py:605): later
        chunks quantize with chunk-1 scales, so K/V values above the
        stored amax saturate at +/-127 with no other trace. SAMPLED —
        the chunk loop calls this once per prompt (its last rest chunk,
        plus one baseline read of the scale-setting first chunk), so the
        cost is two small device->host reads per prompt, not per chunk.
        Warns ONCE when the rest-chunk top-bin rate exceeds
        max(1%, 3 x the first chunk's own top-bin rate) — the first
        chunk's rate is the legitimate near-amax baseline, so growth
        beyond it indicates real saturation, not a peaked distribution."""
        counts = self._topbin_counts(bt_row, dec, nvalid)
        if counts is None:
            return
        clipped, total = counts
        self._tele.on_cachekv(clipped, total)
        rate = clipped / max(total, 1)
        threshold = max(0.01, 3.0 * (baseline or 0.0))
        if rate > threshold and not self._tele.warned_cachekv_clip:
            self._tele.warned_cachekv_clip = True
            import warnings
            warnings.warn(
                f"cachekv-int8 chunked prefill: {rate:.1%} of a later "
                f"chunk's K/V entries sit in the top quantization bin "
                f"(baseline {0.0 if baseline is None else baseline:.1%}) "
                f"— values likely exceed the first-chunk scales "
                f"(documented first-window semantics); long-prompt "
                f"accuracy may degrade. stats()['cachekv_clip_rate'] "
                f"tracks the sampled rate.",
                RuntimeWarning, stacklevel=2)

    def _store_slot_scales(self, slot, seq_scales):
        """Copy a 1-sequence prefill's per-layer scale dicts into the
        slot's host-owned scale rows (decode reads them from the state)."""
        for li, sc in enumerate(seq_scales):
            for k in ("kq", "vq", "kdq", "vdq"):
                self._scales_np[li][k][slot] = np.asarray(sc[k]._data)[0]
        self._scales_dirty = True

    def _decode_attention_series(self, model, pool):
        """(path, counter) for executables built from ``model``'s
        ``paged_decode_step`` over ``pool``: the word is the model's own
        (``paged_decode_attention_path``; a family without the decode
        entry gathers), the counter is
        ``serving_decode_attention_launches_total{path=...}``."""
        from ..observability.metrics import get_registry
        route = getattr(model, "paged_decode_attention_path", None)
        path = route(pool) if route is not None else "gather"
        return path, get_registry().counter(
            "serving_decode_attention_launches_total",
            "launches of a decode-step executable, by the route its "
            "attention takes (kernel: Pallas paged kernel; gather: XLA)",
            labelnames=("engine", "path")).labels(
                engine=self._engine, path=path)

    def _kv_write_series(self, model, pool):
        """(writer, counter) beside ``_decode_attention_series``: the word
        is the model's (``paged_kv_writer``; a family without it scatters
        rows), the counter ``serving_kv_write_launches_total{writer=...}``."""
        from ..observability.metrics import get_registry
        word = getattr(model, "paged_kv_writer", None)
        writer = word(pool) if word is not None else "row"
        return writer, get_registry().counter(
            "serving_kv_write_launches_total",
            "launches of a decode-step executable, by how it writes the "
            "step's K/V rows (page: whole pages read, changed and written "
            "back; row: a row scatter)",
            labelnames=("engine", "writer")).labels(
                engine=self._engine, writer=writer)

    def stats(self) -> Dict[str, float]:
        """The base counters, and ``decode_attention_path`` and
        ``kv_writer``: the labels of this batcher's
        ``serving_decode_attention_launches_total`` and
        ``serving_kv_write_launches_total``."""
        return dict(super().stats(), decode_attention_path=self._decode_path,
                    kv_writer=self._kv_writer)

    def _sync_tables(self):
        import paddle_tpu as paddle
        with _span("serving.sync_tables"):
            self._state["block_tables"] = paddle.to_tensor(self._bt)
            self._state["dec_lens"] = paddle.to_tensor(self._dec)
            # a compiled step returns the pass-through python ints as 0-d
            # arrays; restore them so the NEXT call's signature (and its
            # executable) stays identical
            self._state["block_size"] = self.block_size
            self._state["capacity"] = self.blocks_per_seq * self.block_size
            if self._groups:
                self._state["group_tables"] = self._group_tables()
            if self._position_axes:
                self._state["position_ids"] = self._positions(self._dec)
            if self.cache_quant and self._scales_dirty:
                # scales change only at admit/release — skip the L x 4
                # re-uploads on the steady-state decode path
                self._state["cache_scales"] = [
                    {k: paddle.to_tensor(layer[k]) for k in layer}
                    for layer in self._scales_np]
                self._scales_dirty = False

    def _preempt_latest(self, protect: int) -> bool:
        """Evict the most-recently admitted active request (≠ protect) back
        to the FRONT of the queue; its pages return to the pool. Returns
        False when no victim exists."""
        for slot in reversed(self._admit_order):
            if slot == protect:
                continue
            req = self._slot_req.pop(slot)
            req.slot = None
            self._release_slot(slot)
            with self._intake:
                self._pending.insert(0, req)
            self._trace_close(req, preempted=1)
            self._tele.on_preempt()
            return True
        return False

    def _grow_for_step(self):
        """ondemand: every active slot is about to write kv row dec[slot];
        back it with a page, preempting if the pool is dry."""
        with _span("serving.grow"):
            for slot in list(self._admit_order):
                if slot not in self._slot_req:
                    continue
                while not self._alloc_pages(slot, int(self._dec[slot]) + 1):
                    if self._promo is not None:
                        # an in-flight promotion loses the race to live
                        # decode: reclaim its reserved pages before touching
                        # any live request (its admission full-prefills)
                        self._cancel_promotion(deny=True)
                        continue
                    if self._preempt_latest(protect=slot):
                        continue
                    raise RuntimeError(
                        f"page pool exhausted: slot {slot} needs a page at "
                        f"row {int(self._dec[slot])}, no free pages and no "
                        f"other request to preempt (n_pages={self.n_pages})")

    def _admission_plan(self, req: Request, m_rows: int = 0):
        """Admission's resume-ids / chunk-padding / page-budget arithmetic.
        ``m_rows`` is the cached-prefix row count: only the SUFFIX is
        prefilled, so chunk/ladder padding applies to the suffix and is
        clamped to the capacity left after the cached rows (pad rows past
        capacity would clip-index the block table and corrupt the last
        real page)."""
        ids_np = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int64)]) \
            if req.tokens else req.prompt
        L = len(ids_np)
        S = L - m_rows
        cap = self.blocks_per_seq * self.block_size
        if self.prefill_chunk:
            pad_s = min(-(-S // self.prefill_chunk) * self.prefill_chunk,
                        cap - m_rows)
        elif self._prompt_ladder is not None:
            pad_s = min(self._prompt_ladder.bucket(S), cap - m_rows)
        else:
            pad_s = S
        padded_len = m_rows + pad_s
        if self.policy == "reserve":
            upto = max(padded_len, L + req.max_new_tokens - len(req.tokens))
        else:
            upto = max(padded_len, L + 1)
        return ids_np, L, padded_len, upto

    def _advance_decoders(self, chosen, finished: List[int]):
        """Consume what a decode step handed back for selection: advance
        timelines, append the next tokens, evict finished slots.

        ``serving.fetch`` is the host's wait for the step and one copy:
        under greedy the [B] int32 ids the executable chose (4 B bytes),
        with ``do_sample`` the [B, V] logits; a model's step counts come
        in the same wait. ``serving.pick`` is ``_pick`` (nothing to do
        under greedy) and the per-request loop."""
        with _span("serving.fetch"):
            counts = self._state["layers"]["step_counts"]._data \
                if self._step_counts else None
            if counts is not None:
                counts.copy_to_host_async()
            fetched = np.asarray(chosen._data)
        if counts is not None:
            self._add_step_counts(np.asarray(counts))
        with _span("serving.pick"):
            self._dec += np.asarray(self._slot_active_mask(), np.int32)
            next_tok = self._pick(fetched)
            self._count_picks(fetched, len(self._slot_req))
            for slot, req in list(self._slot_req.items()):
                tok = int(next_tok[slot])
                req.tokens.append(tok)
                self._tele.on_token(req)
                self._last_tok[slot] = tok
                if self._maybe_finish(req, tok):
                    finished.append(req.rid)

    def _step_prologue(self):
        """Shared pre-decode bookkeeping: on-demand page growth, step
        counters, and the host->device table sync. The HOST owns the
        block table and the timeline: re-uploading both every step (tiny
        int32 arrays) keeps parked slots from drifting — the device step
        increments dec_lens for all B slots, the host only for active
        ones."""
        if self.policy == "ondemand":
            self._grow_for_step()
        if self._groups:
            # every running slot is about to write row dec[slot]
            with _span("serving.release_window"):
                for slot in self._slot_req:
                    self._group_rows(slot, int(self._dec[slot]),
                                     int(self._dec[slot]) + 1)
                self._count_groups()
        self._tele.on_step()
        self._tele.on_occupancy(len(self._slot_req))
        self._tele.set_gauges(len(self._pending), len(self._slot_req))
        self._sync_tables()

    def _decode_tail(self, finished: List[int]):
        """The step's decode launch: a speculative round where a draft
        model was given and the round can run, else one plain step."""
        import paddle_tpu as paddle
        if not self._slot_req:
            return
        if self.draft_model is not None \
                and self._speculative_tail(finished):
            return
        self._step_prologue()
        n_active = len(self._slot_req)
        t0 = _time.perf_counter()
        with _span("serving.launch"), paddle.no_grad():
            self._decode_launch_c.inc()
            self._kv_write_c.inc()
            self._count_slot_state_step()
            tok_t = paddle.to_tensor(self._last_tok)
            chosen, self._state = self._step_fn(tok_t, self._state)
        self._advance_decoders(chosen, finished)
        self._tele.on_decode_time(_time.perf_counter() - t0,
                                  tokens=n_active)

    # -- in-batcher speculative decoding ------------------------------------
    def _sync_draft_tables(self):
        import paddle_tpu as paddle
        self._dstate["block_tables"] = paddle.to_tensor(self._bt)
        self._dstate["dec_lens"] = paddle.to_tensor(self._ddec)
        self._dstate["block_size"] = self.block_size
        self._dstate["capacity"] = self.blocks_per_seq * self.block_size

    @staticmethod
    def _argmax_b(logits) -> np.ndarray:
        with _span("serving.fetch"):
            logits_np = np.asarray(logits._data)
        return logits_np.argmax(-1)

    def _speculative_tail(self, finished: List[int]) -> bool:
        """One batched draft/verify round for every active slot; returns
        False (nothing ran) when this round must fall back to the plain
        per-step path, which keeps sole ownership of preemption policy.

        Invariants (the _speculative_loop contract, per slot): the TARGET
        pool holds rows for prompt + tokens[:-1] (``_dec``; tokens[-1] is
        pending), the DRAFT pool holds correct rows for the first
        ``_ddec`` positions. The round appends the draft's catch-up
        (``seq[_ddec:]``, ending at the pending token — its last logits
        are proposal 1), runs k-1 draft steps, then the target scores
        pending + all k proposals in ONE verify pass; each slot accepts
        its longest matching prefix plus the target's own correction, so
        output is the target's greedy sequence token for token."""
        import paddle_tpu as paddle
        reqs = list(self._slot_req.items())
        k = min(self.draft_k,
                min(r.max_new_tokens - len(r.tokens)
                    for _, r in reqs) - 1)
        if k < 1:
            # some slot has budget for exactly one token: a k-wide round
            # would overshoot it, so take one plain step instead
            self.spec_stats["fallback_steps"] += 1
            return False
        cap = self.blocks_per_seq * self.block_size
        cus = {slot: int(self._dec[slot]) - int(self._ddec[slot]) + 1
               for slot, _ in reqs}
        W = self._cu_ladder.bucket(max(cus.values()))
        for slot, _ in reqs:
            # both pools write rows through dec+k; catch-up pad rows
            # reach ddec+W-1 — past-capacity writes would clip-index the
            # block table onto the last REAL page
            if int(self._dec[slot]) + k + 1 > cap \
                    or int(self._ddec[slot]) + W > cap:
                self.spec_stats["fallback_steps"] += 1
                return False
        if self.policy == "ondemand":
            # probe-then-alloc over ALL slots: a declined round must not
            # strand pages it already moved
            plan = []
            need = 0
            for slot, _ in reqs:
                upto = int(self._dec[slot]) + k + 1
                have = int(np.sum(self._bt[slot] != self._scratch))
                need += max(0, self._pages_for(upto) - have)
                plan.append((slot, upto))
            if need > self._available_pages():
                self.spec_stats["fallback_steps"] += 1
                return False
            for slot, upto in plan:
                if not self._alloc_pages(slot, upto):  # pragma: no cover
                    raise RuntimeError("page accounting bug: speculative "
                                       "probe passed but allocation "
                                       "failed")
        self._step_prologue()
        t0 = _time.perf_counter()
        B = self.max_batch
        cu_ids = np.zeros((B, W), np.int64)
        cu_at = np.zeros((B,), np.int32)
        dbase = np.zeros((B,), np.int32)
        for slot, req in reqs:
            seq = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int64)])
            lo = int(self._ddec[slot])
            cu = seq[lo:]                       # ends at the pending token
            cu_ids[slot, :len(cu)] = cu
            cu_at[slot] = len(cu) - 1
            dbase[slot] = lo
        with paddle.no_grad():
            self._sync_draft_tables()
            with _span("serving.launch"):
                dl, self._dstate["layers"] = self._catchup_fn(
                    paddle.to_tensor(cu_ids), self._dstate["layers"],
                    self._dstate["block_tables"], paddle.to_tensor(dbase),
                    paddle.to_tensor(cu_at))
            props = [self._argmax_b(dl)]        # [B] proposal 1
            for slot, _ in reqs:
                self._ddec[slot] = int(self._dec[slot]) + 1
            self._dstate["dec_lens"] = paddle.to_tensor(self._ddec)
            tok = props[0]
            for _ in range(k - 1):
                with _span("serving.launch"):
                    self._draft_launch_c.inc()
                    dlg, self._dstate = self._dstep_fn(
                        paddle.to_tensor(tok.astype(np.int64)),
                        self._dstate)
                tok = self._argmax_b(dlg)
                props.append(tok)
            ids_v = np.zeros((B, k + 1), np.int64)
            for slot, _ in reqs:
                ids_v[slot, 0] = self._last_tok[slot]
                for i in range(k):
                    ids_v[slot, 1 + i] = props[i][slot]
            with _span("serving.launch"):
                vlogits, self._state["layers"] = self._verify_fn(
                    paddle.to_tensor(ids_v), self._state["layers"],
                    self._state["block_tables"],
                    paddle.to_tensor(self._dec.copy()))
        g = self._argmax_b(vlogits)                       # [B, k+1]
        with _span("serving.pick"):
            total = self._accept_round(reqs, props, g, k, finished)
        self.spec_stats["rounds"] += 1
        self._tele.on_decode_time(_time.perf_counter() - t0,
                                  tokens=total)
        return True

    def _accept_round(self, reqs, props, g, k: int,
                      finished: List[int]) -> int:
        """Each slot accepts its longest matching prefix of the draft's
        proposals plus the target's own correction; returns the tokens
        appended over all slots."""
        total = 0
        for slot, req in reqs:
            pv = [int(props[i][slot]) for i in range(k)]
            j = 0
            while j < k and pv[j] == int(g[slot, j]):
                j += 1
            acc = pv[:j] + [int(g[slot, j])]
            self.spec_stats["proposed"] += k
            self.spec_stats["matched"] += j
            sp = req.spans.get("decode")
            if sp is not None:
                # per-request accept accounting on the OPEN decode span
                # (before _maybe_finish can close it): the goodput
                # ledger prices rejected draft tokens from these
                tg = sp.tags
                tg["spec_proposed"] = int(tg.get("spec_proposed")
                                          or 0) + k
                tg["spec_matched"] = int(tg.get("spec_matched")
                                         or 0) + j
                tg["spec_rounds"] = int(tg.get("spec_rounds") or 0) + 1
            old_dec = int(self._dec[slot])
            self._dec[slot] = old_dec + len(acc)
            self._ddec[slot] = min(old_dec + k, old_dec + len(acc))
            for t in acc:
                if req.finished:
                    break            # EOS mid-round: discard the rest
                req.tokens.append(int(t))
                self._tele.on_token(req)
                self._last_tok[slot] = int(t)
                total += 1
                if self._maybe_finish(req, int(t)):
                    finished.append(req.rid)
        return total

    # -- the engine ---------------------------------------------------------
    def _step_impl(self) -> List[int]:
        """Admit, grow pages (ondemand), decode one token per active slot,
        evict finished. Returns rids finishing during THIS call."""
        finished = self._admit()
        self._decode_tail(finished)
        return finished

    def _slot_active_mask(self):
        m = np.zeros((self.max_batch,), bool)
        for slot in self._slot_req:
            m[slot] = True
        return m
