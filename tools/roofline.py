#!/usr/bin/env python
"""Roofline model for the bench.py training configs (VERDICT r3 #1).

Computes, from first principles (no hardware needed), where a training
step's time must go on a v5e chip: MXU FLOPs, HBM traffic per step
(weights fwd/bwd, optimizer-state update, saved activations, logits),
the resulting compute/memory time bounds, and the measured-MFU ceiling
those bounds imply. After a chip run, compare `BENCH_TPU_SNAPSHOT`
against `ROOFLINE.json`: measured step time ~ compute bound -> MXU-bound
and healthy; >> bound -> the gap names the suspect (opt traffic,
attention workspace, remat replay).

Peak numbers: v5e ~197 TFLOP/s bf16, ~819 GB/s HBM (public chip specs)
by default.  If tools/measure_peaks.py has captured MEASURED_PEAKS.json
on real hardware (VERDICT r4 item 3), the measured peaks are used
instead and the output carries `"measured": true` plus a
modeled-vs-measured comparison block, so the ceiling reflects what the
chip delivers through our stack rather than the datasheet.

Usage: python tools/roofline.py   # prints table + writes ROOFLINE.json
"""
from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "ROOFLINE.json")
PEAKS = os.path.join(REPO, "MEASURED_PEAKS.json")

DATASHEET_FLOPS = 197e12      # v5e bf16
DATASHEET_HBM = 819e9         # v5e bytes/s

PEAK_FLOPS = DATASHEET_FLOPS
PEAK_HBM = DATASHEET_HBM
MEASURED = None
if os.path.exists(PEAKS):
    try:
        _p = json.load(open(PEAKS))
        # read every required key BEFORE claiming measured peaks: a
        # malformed/partial capture must leave the datasheet numbers AND
        # measured:false, never a half-applied mix
        if _p.get("tpu"):
            _flops = float(_p["matmul_tflops"]) * 1e12
            _hbm = float(_p["hbm_gbps"]) * 1e9
            MEASURED = _p
            PEAK_FLOPS, PEAK_HBM = _flops, _hbm
    except (ValueError, KeyError, TypeError):
        pass


def llama_params(V, H, I, L, heads, kv_heads):
    head_dim = H // heads
    attn = H * (heads * head_dim) + 2 * H * (kv_heads * head_dim) \
        + (heads * head_dim) * H
    mlp = 3 * H * I
    return V * H * 2 + L * (attn + mlp + 2 * H) + H


def analyze(name, V, H, I, L, heads, kv_heads, batch, seq, remat):
    P = llama_params(V, H, I, L, heads, kv_heads)
    tokens = batch * seq
    att_flops_tok = 12 * L * H * seq          # bench.py's MFU formula term
    flops_counted = (6 * P + att_flops_tok) * tokens
    # real executed FLOPs: selective remat replays elementwise (~free) but
    # the flash custom-vjp recomputes the attention forward in the
    # backward (+4*L*H*seq per token); full remat replays the whole
    # forward (+2P per token)
    replay = {"selective": 4 * L * H * seq, "full": 2 * P + 4 * L * H * seq,
              "off": 0}[remat] * tokens
    flops_real = flops_counted + replay

    wbytes = 2 * P                             # bf16 weights
    # HBM traffic per step (bytes):
    traffic = {
        # fwd reads weights once; bwd reads them for dgrad + wgrad
        "weights_fwd_bwd": 3 * wbytes,
        # AdamW multi-precision: read master+m+v+grad(f32), write
        # master+m+v(f32) + bf16 params
        "optimizer_update": (4 + 3) * 4 * P + 2 * P,
        # saved activations (selective: the no-batch-dim dot outputs),
        # written in fwd + read in bwd
        "saved_activations": 2 * _saved_bytes(H, I, L, tokens, remat),
        # logits fp32 + softmax grad traffic (write + read + grad)
        "logits": 3 * tokens * V * 4,
    }
    total_bytes = sum(traffic.values())

    t_compute = flops_real / PEAK_FLOPS
    t_memory = total_bytes / PEAK_HBM
    # perfectly-overlapped lower bound on step time
    t_step = max(t_compute, t_memory)
    tok_per_s = tokens / t_step
    # bench.py counts flops_counted: the measured-MFU ceiling
    mfu_ceiling = flops_counted / (t_step * PEAK_FLOPS)
    return {
        "config": name, "params": P, "batch": batch, "seq": seq,
        "remat": remat,
        "flops_counted": flops_counted, "flops_real": flops_real,
        "hbm_bytes": traffic | {"total": total_bytes},
        "t_compute_ms": round(t_compute * 1e3, 2),
        "t_memory_ms": round(t_memory * 1e3, 2),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "tokens_per_s_bound": round(tok_per_s, 0),
        "measured_mfu_ceiling": round(mfu_ceiling, 3),
    }


def _saved_bytes(H, I, L, tokens, remat):
    if remat == "full":
        return tokens * H * 2 * L              # layer inputs only
    # selective: qkv (3H) + o (H) + gate/up (2I) + down (H) per layer, bf16
    per_tok_layer = (3 * H + H + 2 * I + H) * 2
    return tokens * per_tok_layer * L


BENCH_CONFIGS = [
    # (V, H, I, L, heads, kvh, batch, seq, remat). "large" mirrors bench.py
    # (tests/test_perf.py pins it); "small" is the config of the one chip
    # number on record (BENCH_TPU_SNAPSHOT.json) and "medium" lies between.
    ("large", 32000, 1536, 4096, 16, 12, 12, 4, 2048, "selective"),
    ("medium", 32000, 1152, 3072, 16, 9, 9, 4, 2048, "selective"),
    ("small", 32000, 1024, 2816, 24, 16, 16, 4, 1024, "off"),
]


def main():
    rows = [analyze(*cfg) for cfg in BENCH_CONFIGS]
    for r in rows:
        print(f"{r['config']:7s} P={r['params']/1e6:6.0f}M "
              f"{r['bound']}-bound  t_mxu={r['t_compute_ms']:7.2f}ms "
              f"t_hbm={r['t_memory_ms']:6.2f}ms  "
              f"<= {r['tokens_per_s_bound']:8.0f} tok/s  "
              f"MFU ceiling {r['measured_mfu_ceiling']}")
    out = {"peak_flops": PEAK_FLOPS, "peak_hbm": PEAK_HBM,
           "measured": MEASURED is not None, "configs": rows}
    if MEASURED is not None:
        out["peaks_source"] = {
            "captured_at": MEASURED.get("captured_at"),
            "device": MEASURED.get("device"),
            "modeled_vs_measured": {
                "flops": [DATASHEET_FLOPS, PEAK_FLOPS],
                "hbm": [DATASHEET_HBM, PEAK_HBM],
            },
        }
        print(f"peaks: MEASURED {PEAK_FLOPS/1e12:.0f} TFLOP/s "
              f"{PEAK_HBM/1e9:.0f} GB/s (datasheet "
              f"{DATASHEET_FLOPS/1e12:.0f}/{DATASHEET_HBM/1e9:.0f})")
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    os.replace(tmp, OUT)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
