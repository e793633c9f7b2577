"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s in bfloat16 and 819 GB/s of HBM bandwidth.  A
device that is not in the table is an error, not a default: a roofline
share against a guessed peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bfloat16 matmul
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; raises for a device
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py "
                         f"with their source") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple:
    """(share of the roofline in %, the bound that binds): the least time
    the chip could take for ``flops`` operations and ``nbytes`` of HBM
    traffic, over the measured ``seconds``."""
    p = peaks(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
