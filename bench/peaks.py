"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time for work of ``flops`` and ``nbytes``, and
    which bound sets it ("compute" or "memory")."""
    t_compute = flops / peaks["bf16_flops"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory,
                                                                  "memory")
