"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s. The FLOP/s figure is
the matrix units' bf16 peak; the solvers' float32 element-wise work runs on
the vector unit, whose rate is lower, so the compute term of a roofline
taken against it is a lower bound on the time, never an upper one.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to bench/peaks.py with its source") from None


def least_seconds(work: dict, device_kind: str) -> float:
    """The least time the chip allows for `work` ({"flops", "bytes"} on one
    chip): the larger of its byte and its operation bound."""
    p = peaks(device_kind)
    return max(work["bytes"] / p["hbm_bytes_per_s"], work["flops"] / p["flops_per_s"])
