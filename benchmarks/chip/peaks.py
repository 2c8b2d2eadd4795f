"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A kind that is not here is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}") from None


def roofline_s(peak: dict, flops: float, nbytes: float) -> float:
    """The least time the chip could take for ``flops`` bf16 operations that
    move ``nbytes`` through HBM: the larger of the two over their peaks."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
