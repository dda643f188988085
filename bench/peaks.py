"""Published peaks of each accelerator the benchmark runs on, by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

The f32 entry is derived, not published: the chain kernels ask the MXU for
``Precision.HIGHEST`` on f32 operands, which XLA and Mosaic run as six bf16
passes, so the f32 peak is the bf16 peak divided by six.
"""
from __future__ import annotations

V5E = {
    "source": "Google Cloud documentation, TPU v5e system architecture",
    "flops": {
        "bfloat16": 197e12,
        "int8": 393e12,
        # derived: six bf16 MXU passes per f32 product at Precision.HIGHEST
        "float32": 197e12 / 6,
    },
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
}

PEAKS = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def least_time_s(flops: float, byts: float, peaks: dict, dtype: str = "bfloat16") -> tuple[float, str]:
    """The least time the chip could take for ``flops`` and ``byts``, and
    which bound sets it (``"compute"`` or ``"memory"``)."""
    t_c = flops / peaks["flops"][dtype]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
