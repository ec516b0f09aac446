"""Published peaks of the chips this benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error,
never a default.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
(Copied from ``skypilot_tpu/observability/attribution.py::PEAKS`` so that
the program cannot move the yardstick; the original stays for the
program's own gauges.)
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to benchmarks/peaks.py with its source") from None
