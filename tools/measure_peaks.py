#!/usr/bin/env python
"""Measure achieved device peaks: bf16 matmul TFLOP/s + HBM stream GB/s.

VERDICT r4 item 3: the roofline model (tools/roofline.py) assumes v5e
datasheet peaks (197 TFLOP/s bf16, 819 GB/s HBM). This tool measures
what the chip actually delivers through our stack so the roofline's
ceiling is grounded in reality, the way the reference autotunes against
the device rather than a spec sheet
(paddle/phi/kernels/autotune/switch_autotune.cc).

Two microbenchmarks, both plain jitted XLA ops (the op class that has
been hardware-validated since round 3 — no first-contact Mosaic risk):

- matmul: square bf16 matmuls over a size sweep; achieved TFLOP/s =
  2*M*N*K / t.  The max over sizes approximates the MXU peak as seen
  from JAX (includes dispatch overhead at small sizes; large sizes
  amortize it).
- stream: out = x * 2.0 + 1.0 over a ~1 GiB bf16 array; traffic is
  read N + write N bytes.  Achieved GB/s approximates usable HBM
  bandwidth for the fused-elementwise traffic the roofline bills.

Writes MEASURED_PEAKS.json (atomic) and prints one JSON line.  Safe to
run on CPU for plumbing tests (records "tpu": false; roofline ignores
non-TPU captures).

Usage: python tools/measure_peaks.py [--iters 20] [--stream-mib 1024]
"""
from __future__ import annotations

import argparse
import json
import os
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "MEASURED_PEAKS.json")


def _time_fn(fn, *args, iters):
    """Median wall time of fn(*args) over `iters` timed calls (1 warmup)."""
    fn(*args).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def measure_matmul(iters, sizes=(2048, 4096, 6144, 8192)):
    import jax
    import jax.numpy as jnp

    results = []
    for n in sizes:
        key = jax.random.PRNGKey(n)
        a = jax.random.normal(key, (n, n), jnp.bfloat16)
        b = jax.random.normal(key, (n, n), jnp.bfloat16)

        @jax.jit
        def mm(a, b):
            return a @ b

        t = _time_fn(mm, a, b, iters=iters)
        tflops = 2 * n ** 3 / t / 1e12
        results.append({"n": n, "t_ms": round(t * 1e3, 3),
                        "tflops": round(tflops, 1)})
    return results


def measure_dispatch(iters):
    """Median wall time of a trivially-small jitted op, i.e. the
    per-dispatch overhead: with per-token dispatch the ceiling is
    1/dispatch_latency tokens/s/slot regardless of model size."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((8, 8), jnp.float32)

    @jax.jit
    def tiny(x):
        return x + 1.0

    t = _time_fn(tiny, x, iters=max(iters, 10))
    return {"t_ms": round(t * 1e3, 3)}


def measure_stream(iters, mib):
    import jax
    import jax.numpy as jnp

    n = mib * 1024 * 1024 // 2          # bf16 elements
    x = jnp.ones((n,), jnp.bfloat16)

    @jax.jit
    def axpy(x):
        return x * jnp.bfloat16(2.0) + jnp.bfloat16(1.0)

    t = _time_fn(axpy, x, iters=iters)
    traffic = 2 * n * 2                  # read + write, bf16
    return {"mib": mib, "t_ms": round(t * 1e3, 3),
            "gbps": round(traffic / t / 1e9, 1)}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--stream-mib", type=int, default=1024)
    args = p.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    sizes = (2048, 4096, 6144, 8192)
    if not on_tpu:
        # keep CPU plumbing runs cheap (single-core hosts)
        args.iters = min(args.iters, 2)
        args.stream_mib = min(args.stream_mib, 64)
        sizes = (512, 1024)

    mm = measure_matmul(args.iters, sizes)
    st = measure_stream(args.iters, args.stream_mib)
    disp = measure_dispatch(args.iters)
    rec = {
        "tpu": on_tpu,
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "matmul_tflops": max(r["tflops"] for r in mm),
        "hbm_gbps": st["gbps"],
        "dispatch_ms": disp["t_ms"],
        "matmul_sweep": mm,
        "stream": st,
    }
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    os.replace(tmp, OUT)
    print(json.dumps({k: rec[k] for k in
                      ("tpu", "device", "matmul_tflops", "hbm_gbps",
                       "dispatch_ms")}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
