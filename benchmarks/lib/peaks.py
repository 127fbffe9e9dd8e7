"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not listed is an error, never a default.

Copied from ``paddle_tpu/observability/profiler.py:DEVICE_PEAKS`` (PR 21) so
that a later PR cannot move the yardstick; source: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit("benchmark: device kind %r is not in the table of "
                         "peaks (benchmarks/lib/peaks.py)" % device_kind)
