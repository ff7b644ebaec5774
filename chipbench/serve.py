"""Runner kinds ``serve-closed`` and ``serve-open``: the timed path is
``Gateway.submit`` (through ``Gateway.stream``) and ``Gateway.step`` over one
``PagedContinuousBatcher`` replica.

One thread offers the load and drives the gateway, as the gateway's own
control loop is written to be driven. Tokens are read from each request's
streaming session after every step, so a token's time is the time it reached
the host. Once the window (and, in the open loop, the bounded drain) has
closed and the server is freed, the plain reference runs once over a seeded
sample of the finished requests, the longest among them.
"""
from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from . import families
from . import harness as H
from . import traffic as T
from . import weights as W
from .train import set_flags


def build(cfg: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.inference.gateway import Gateway
    from paddle_tpu.inference.serving import PagedContinuousBatcher

    # one program for every --seed: the generator's key ends up as a
    # constant of the compiled step (PERF.md, finding of PR 24), and the
    # weights, ids and order come from --seed through chipbench itself
    paddle.seed(0)
    model = families.of(cfg).program_model(cfg, dtype="bfloat16")
    model.bfloat16()
    model.eval()
    W.install(model, cfg, seed, scanned=False)
    batcher = PagedContinuousBatcher(model, **cfg["runner"]["server"])
    gateway = Gateway()
    gateway.add_replica("chip0", batcher)
    return model, batcher, gateway


class Live:
    """One request in flight, as the load generator sees it."""
    __slots__ = ("offer", "session", "sent", "times", "tokens", "failed")

    def __init__(self, offer, session, sent):
        self.offer, self.session, self.sent = offer, session, sent
        self.times, self.tokens = [], []
        self.failed = False


class Driver:
    def __init__(self, gateway, tracer):
        self.gateway, self.tracer = gateway, tracer
        self.live, self.finished, self.failures = [], [], []
        self.step_ms = []
        self.results = {}

    def send(self, offer) -> None:
        try:
            session = self.gateway.stream(offer.prompt, offer.max_new)
        except Exception as exc:     # shed or refused: one of the failed
            self.failures.append((offer, repr(exc)))
            return
        self.live.append(Live(offer, session, H.clock()))

    def step(self) -> None:
        t = H.clock()
        with self.tracer.span("gateway.step"):
            self.gateway.step()
        now = H.clock()
        self.step_ms.append((now - t) * 1e3)
        still = []
        for r in self.live:
            new = r.session.read_available()
            if new:
                r.tokens.extend(new)
                r.times.extend([now] * len(new))
            if r.session.done:
                try:
                    full = self.gateway.pop_result(r.session.gid)
                    self.results[r.offer.index] = np.asarray(full)
                except Exception as exc:
                    r.failed = True
                    self.failures.append((r.offer, repr(exc)))
                self.finished.append(r)
            else:
                still.append(r)
        self.live = still


def offer_open(drv, stream, seconds: float, t_open: float) -> list:
    """The open loop: send what is due, on wall time, for ``seconds``; step
    the gateway while anything is in flight, else wait for the next arrival.
    Returns how late each request was sent (ms)."""
    late_ms = []
    nxt = next(stream)
    while True:
        now = H.clock() - t_open
        while nxt.due <= now and nxt.due < seconds:
            late_ms.append((H.clock() - t_open - nxt.due) * 1e3)
            drv.send(nxt)
            nxt = next(stream)
        if now >= seconds:
            return late_ms
        if drv.live:
            drv.step()
        else:
            with drv.tracer.span("generator.wait"):
                time.sleep(max(0.0, min(nxt.due, seconds)
                               - (H.clock() - t_open)))


def warm_up(gateway, cfg, seed):
    """Every shape the traffic uses: the one prefill chunk and the one decode
    step, each past its eager first call and its compiled second."""
    w = cfg["runner"]["warmup"]
    rng = T.rng_for(seed, 9)
    for _ in range(w["requests"]):
        gateway.submit(rng.integers(0, cfg["vocab_size"], w["prompt_tokens"]),
                       w["answer_tokens"])
    gateway.run_until_done()


def snapshot(batcher):
    from paddle_tpu.observability.metrics import get_registry
    st = batcher.stats()
    pc = batcher.prefix_cache.stats() if batcher.prefix_cache else {}
    qw = get_registry().histogram(
        "gateway.queue_wait_seconds",
        "gateway-queue residency from submit to dispatch pop",
        labelnames=("lane",)).labels(lane="high")
    return {"steps": st["steps"], "tokens": st["generated_tokens"],
            "occupancy_sum": st["mean_active_slots"] * max(st["steps"], 1),
            "preemptions": st["preemptions"],
            "hit_tokens": pc.get("hit_tokens", 0),
            "miss_tokens": pc.get("miss_tokens", 0),
            "queue_wait_sum": qw.sum, "queue_wait_count": qw.count}


def read_series(series) -> dict:
    """The registry series a family lists as ``(key, name, labels)``: a
    counter's or a gauge's value under ``key``, a histogram's under
    ``key.sum`` and ``key.count``; nothing for a series the program has not
    made yet (it has counted nothing)."""
    from paddle_tpu.observability.metrics import get_registry
    out = {}
    for key, name, labels in series:
        entry = get_registry().get(name)
        if entry is None:
            continue
        if labels:
            entry = entry.labels(**labels)
        if entry.kind == "histogram":
            out[key + ".sum"], out[key + ".count"] = entry.sum, entry.count
        else:
            out[key] = entry.value
    return out


def run(ctx) -> None:
    import jax
    from paddle_tpu.observability import opprof

    args, cfg, traffic = ctx["args"], ctx["cfg"], ctx["traffic"]
    seed, vocab = args.seed, cfg["vocab_size"]
    series = families.of(cfg).COUNTERS
    open_loop = traffic["kind"] == "serve-open"
    set_flags(cfg)
    opprof.enable()
    opprof.reset_captures()
    counter = H.CompileCounter()
    phases = {"start": H.clock() - ctx["t0"]}

    t = H.clock()
    model, batcher, gateway = build(cfg, seed)
    phases["build"] = H.clock() - t
    t = H.clock()
    warm_up(gateway, cfg, seed)
    phases["warm_up"] = H.clock() - t
    batcher.reset_stats()
    gateway.reset_stats()
    H.say("fingerprints", H.program_fingerprints())
    H.say("flash_tilings", H.flash_tilings())

    stream = T.offers(traffic, vocab, seed)
    tracer = ctx["tracer"]
    drv = Driver(gateway, tracer)
    before, stats0 = counter.snapshot(), snapshot(batcher)
    series0 = read_series(series)
    tracer.start()
    setup_s = H.clock() - ctx["t0"]
    t_open = H.clock()
    late_ms = []
    if open_loop:
        late_ms = offer_open(drv, stream, args.seconds, t_open)
    else:
        for offer in itertools.islice(stream, traffic["clients"]):
            drv.send(offer)
        while H.clock() - t_open < args.seconds:
            drv.step()
            for _ in range(traffic["clients"] - len(drv.live)):
                drv.send(next(stream))
    t_close = H.clock()
    window_tokens = sum(sum(1 for x in r.times if x <= t_close)
                        for r in drv.finished + drv.live)
    elapsed = t_close - t_open
    stats1, series1 = snapshot(batcher), read_series(series)
    window_steps = list(drv.step_ms)
    tracer.stop()              # the trace is of the window, not of the drain
    # the drain: requests in flight finish, outside the window
    unfinished = 0
    if open_loop:
        t_drain = H.clock()
        while drv.live and H.clock() - t_drain < traffic["drain_seconds"]:
            drv.step()
        unfinished = len(drv.live)
    in_window = counter.since(before)
    peak = H.memory_peak_bytes(ctx["devices"])
    leaked = batcher.audit_pages() if not drv.live else 0
    H.say("setup_phases_s", {k: round(v, 3) for k, v in phases.items()})
    H.say("step_times", H.step_stats(window_steps))
    H.say("compiles_in_window", in_window)
    if in_window["compiled"]:
        raise SystemExit(f"chipbench: {in_window['compiled']} programs "
                         f"compiled inside the window")

    finished = [r for r in drv.finished if not r.failed]
    for r in finished:       # what was streamed is what the gateway returns
        full = drv.results[r.offer.index]
        if len(r.tokens) != r.offer.max_new or not np.array_equal(
                full, np.concatenate([r.offer.prompt, r.tokens])):
            drv.failures.append((r.offer, "streamed tokens differ from "
                                          "the result"))
            r.failed = True
    finished = [r for r in finished if not r.failed]
    failed = len(drv.failures) + unfinished
    attempted = len(finished) + failed

    # the sample the reference follows: the longest, then a seeded draw,
    # with requests served from a prefix-cache hit among them where the
    # traffic shares prefixes
    sample = pick_sample(finished, traffic["check_requests"], seed)
    batcher.close()
    del model, batcher, gateway, drv.gateway
    gc.collect()
    jax.clear_caches()
    t = H.clock()
    numbers = compare(cfg, seed, traffic, sample, ctx["cell_file"]["limits"],
                      args.control)
    numbers["leaked_pages"] = {"value": float(leaked), "limit": 0.0}
    numbers["failed_requests"] = {"value": float(failed), "limit": 0.0}
    H.say("reference_s", round(H.clock() - t, 3))

    d = {k: stats1[k] - stats0[k] for k in stats0}
    measured = {"setup_s": setup_s}
    if open_loop:
        due = [r for r in finished]
        ttft = [(r.times[0] - (t_open + r.offer.due)) * 1e3 for r in due]
        gaps = [g * 1e3 for r in due for g in np.diff(r.times)]
        measured["ttft_mean_ms"] = float(np.mean(ttft)) if ttft else None
        measured["tpot_mean_ms"] = float(np.mean(gaps)) if gaps else None
        tpot_p95 = float(np.percentile(gaps, 95)) if gaps else None
        H.say("latency", {"requests": len(due), "gaps": len(gaps),
                          "ttft_median_ms": float(np.median(ttft)),
                          "ttft_max_ms": float(np.max(ttft)),
                          "tpot_median_ms": float(np.median(gaps)),
                          "tpot_p95_ms": tpot_p95,
                          "late_mean_ms": float(np.mean(late_ms))})
    else:
        measured["serve_tokens_per_s"] = window_tokens / elapsed
        tpot_p95 = None
    context = sum(len(r.offer.prompt) + j for r in drv.finished + drv.live
                  for j, x in enumerate(r.times) if j and x <= t_close)
    ctx["finish"](correct=H.judge(numbers), attempted=attempted,
                  failed=failed, measured=measured, numbers=numbers,
                  peak=peak,
                  run={"elapsed_s": elapsed, "step_ms": window_steps,
                       "window_tokens": window_tokens, "late_ms": late_ms,
                       "tpot_p95_ms": tpot_p95,
                       "decode_steps": d["steps"],
                       "occupancy_sum": d["occupancy_sum"],
                       "hit_tokens": d["hit_tokens"],
                       "miss_tokens": d["miss_tokens"],
                       "queue_wait_sum": d["queue_wait_sum"],
                       "queue_wait_count": d["queue_wait_count"],
                       "decode_context_tokens": context,
                       "counters": {k: v - series0.get(k, 0)
                                    for k, v in series1.items()}})


def pick_sample(finished, n: int, seed: int):
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r.offer.prompt)
                                             + r.offer.max_new))
    chosen = [order[0]]
    hits = [r for r in order[1:] if r.offer.shared]
    rest = [r for r in order[1:] if not r.offer.shared]
    rng = T.rng_for(seed, 7)
    for pool, k in ((hits, n // 2), (rest, n)):
        pool = [pool[i] for i in rng.permutation(len(pool))]
        chosen += pool[:max(0, min(k, n - len(chosen)))]
    return chosen[:n]


def token_gaps(ref_logits, tokens) -> list:
    """For every position, how far the given token's logit lies below the
    reference's best (0 where it is the reference's own choice)."""
    return [float(lg[j].max() - lg[j, int(tok)])
            for lg, toks in zip(ref_logits, tokens)
            for j, tok in enumerate(toks)]


def compare(cfg, seed, traffic, sample, limits, control: bool) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sample."""
    from . import reference as R
    if not sample:
        return {"served_tokens_compared": {"value": 0.0, "limit": -1.0}}
    pad = -(-T.longest(traffic) // 128) * 128
    ids = np.zeros((len(sample), pad), np.int64)
    rows = []
    for i, r in enumerate(sample):
        seq = np.concatenate([r.offer.prompt, r.tokens])
        ids[i, :len(seq)] = seq
        p = len(r.offer.prompt)
        rows.append(list(range(p - 1, p - 1 + len(r.tokens))))
    ref = R.served_logits(cfg, seed, ids, rows)
    gaps = token_gaps(ref, [r.tokens for r in sample])
    n_tok = len(gaps)
    numbers = {"served_logit_gap": {"value": max(gaps),
                                    "limit": limits["served_logit_gap"]}}
    H.say("compared_sample", {
        "requests": len(sample), "tokens": n_tok,
        "prompt_tokens": [len(r.offer.prompt) for r in sample],
        "from_prefix_hit": sum(bool(r.offer.shared) for r in sample),
        "tokens_not_reference_argmax": sum(g > 0 for g in gaps),
        "gap_p50": float(np.median(gaps)),
        "gap_p99": float(np.percentile(gaps, 99))})
    if control:
        low = R.served_logits(cfg, seed, ids, rows, precision="fp8")
        cgaps = token_gaps(ref, [lo.argmax(-1) for lo in low])
        H.say("control", {"served_logit_gap": max(cgaps),
                          "gap_p50": float(np.median(cgaps)),
                          "gap_p99": float(np.percentile(cgaps, 99)),
                          "tokens_not_reference_argmax":
                              sum(g > 0 for g in cgaps)})
    return numbers
