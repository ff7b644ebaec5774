"""Published peaks of one chip, keyed by a substring of JAX's ``device_kind``.

The one table every utilization in this benchmark divides by. A device that is
not in it is an error, never a default. Copied from
``paddle_tpu/utils/flops.py::PEAK_BF16_FLOPS`` (see PERF.md, Open questions)
with the memory bandwidth added.
"""
from __future__ import annotations

# source: Google Cloud TPU documentation, system-architecture page of each
# generation ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).
PEAKS = {
    "v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16 * 2 ** 30},
    "v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
            "hbm_bytes": 16 * 2 ** 30},
}


def peaks_for(device_kind: str) -> dict:
    kind = device_kind.lower()
    for key, row in PEAKS.items():
        if key in kind:
            return row
    raise ValueError(f"device_kind {device_kind!r} is not in "
                     f"chipbench/peaks.py: add its published peaks")
