"""What every runner kind shares: the device assertion, the compile cache and
the compile counter, the trace window, step statistics, the per-layer metric
readers found by name, and the last line."""
from __future__ import annotations

import contextlib
import hashlib
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPANS = ("train.step", "gateway.step", "generator.wait")


def say(key, value):
    """An earlier line of the run: ``key: value`` on standard output."""
    text = value if isinstance(value, str) else json.dumps(value,
                                                            default=float)
    print(f"{key}: {text}", flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_config(name: str, rehearse: bool) -> dict:
    cfg = load_json("configs", f"{name}.json")
    if rehearse:
        cfg = dict(cfg)
        tiny = load_json("rehearse", f"{name}.json")
        for key, value in tiny.items():
            cfg[key] = ({**cfg[key], **value}
                        if isinstance(value, dict) and key in cfg else value)
    return cfg


def require_chip(chips: int, rehearse: bool):
    """No chip, no number: a TPU whose kind the peaks table knows, and as
    many as the cell asks for. Only --rehearse runs elsewhere."""
    import jax
    from .peaks import peaks_for
    devices = jax.devices()
    if rehearse:
        return devices[:chips], None
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX reports "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"reports {len(devices)}")
    return devices[:chips], peaks_for(devices[0].device_kind)


def enable_compile_cache() -> str:
    """JAX's persistent cache at the one fixed place: where
    JAX_COMPILATION_CACHE_DIR says, else inside this checkout. The program
    fixes the same directory in code, so it is handed the choice through its
    own entry point."""
    from paddle_tpu.perf.compile_cache import enable_persistent_cache
    return enable_persistent_cache()


class CompileCounter:
    """Counts what JAX compiled, loaded from the cache and traced, so that a
    window can show it did none of it."""

    _EVENTS = {"/jax/core/compile/backend_compile_duration": "compiled",
               "/jax/compilation_cache/cache_retrieval_time_sec": "loaded",
               "/jax/core/compile/jaxpr_trace_duration": "traced"}

    def __init__(self):
        import jax
        self.counts = {"compiled": 0, "loaded": 0, "traced": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.counts.items()}


def memory_peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    known = [p for p in peaks if p]
    if known:
        return max(known)
    if devices[0].platform == "tpu":
        return None
    import resource          # a --rehearse run off the chip: the process's
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def step_stats(ms) -> dict:
    """Minimum, median and maximum step time and the steps over 1.5x the
    median: tells a stall of a few steps from every step being slower."""
    ms = list(ms)
    if not ms:
        return {"n": 0}
    med = statistics.median(ms)
    return {"n": len(ms), "min_ms": min(ms), "median_ms": med,
            "max_ms": max(ms),
            "over_1.5x_median": int(sum(m > 1.5 * med for m in ms))}


def text_fingerprint(hlo_text: str) -> str:
    return hashlib.sha256(hlo_text.encode()).hexdigest()[:16]


def program_fingerprints() -> dict:
    """Per executable the program compiled for its timed paths: the hash of
    its optimized HLO text, as ``observability.opprof`` captured it at the
    warm transition."""
    from paddle_tpu.observability import opprof
    out = {}
    for label, profs in opprof.get_captures().items():
        table = [[(r["op"], r["class"], r["flops"], r["bytes"], r["count"])
                  for r in p.ops] for p in profs]
        out[label] = {
            "hlo_text": sorted({p.fingerprint for p in profs}),
            "ops": sorted({text_fingerprint(json.dumps(t)) for t in table}),
            "captures": len(profs)}
    return out


def flash_tilings() -> dict:
    from paddle_tpu.core.flags import get_flag
    from paddle_tpu.ops.pallas import autotune, flash_attention
    return {"block_q": flash_attention.DEFAULT_BLOCK_Q,
            "block_k": flash_attention.DEFAULT_BLOCK_K,
            "FLAGS_flash_autotune": bool(get_flag("FLAGS_flash_autotune")),
            "autotuned_shapes": len(autotune._BEST)}


class Tracer:
    """The profiler around the window of a --trace 1 run; nothing otherwise.
    Spans are jax.profiler.TraceAnnotation, written into the same trace."""

    def __init__(self, on: bool, workload: str):
        self.on = on
        self.dir = os.path.join(ROOT, ".chipbench", "trace", workload)
        self._window = None

    def span(self, name):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        if not self.on:
            return
        import jax
        from .xplane import WINDOW_SPAN
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    def stop(self):
        if not self.on:
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        from . import xplane
        path = xplane.newest_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"no trace was written under {self.dir}")
        return xplane.reduce(xplane.load(path), SPANS), path


def read_metric(name: str, run: dict):
    """The reader of one per-layer metric: ``chipbench/metrics/<name>.py``
    with a ``read(run)`` that returns the value, or None where it finds
    nothing to read."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def report(numbers: dict) -> str:
    """Each number compared beside its limit, short and plain."""
    return "compared: " + ", ".join(
        f"{k}={v['value']:.6g} (limit {v['limit']:.6g})"
        for k, v in numbers.items())


def judge(numbers: dict) -> bool:
    return all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in numbers.values())


def finish(line: dict, numbers: dict, bench: dict, workload: str,
           trace: bool, chips: int, optional=()):
    """Check the last line against the contract, print the numbers compared
    on standard error and the line on standard output. Exits non-zero with
    the reason if the line would be refused."""
    from .lastline import problems
    line["compared"] = numbers               # comes last in the line
    bad = problems(line, bench, workload, trace, chips, optional)
    if bad:
        print("chipbench: the last line would be refused: "
              + "; ".join(bad), file=sys.stderr, flush=True)
        print(json.dumps(line, default=float), file=sys.stderr, flush=True)
        raise SystemExit(4)
    print(report(numbers), file=sys.stderr, flush=True)
    print(json.dumps(line, default=float), flush=True)


def clock() -> float:
    return time.perf_counter()
