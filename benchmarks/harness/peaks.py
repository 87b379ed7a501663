"""Published peaks of the chips the benchmark may run on, keyed by
`jax.Device.device_kind`. A kind that is not here is an error, never a
default: an assumed peak makes every share of it a guess.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" (197
TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s; the runtime
names the chip "TPU v5 lite"). Copied from `bench.PEAK_FLOPS`, with the
memory figures added.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device kind {kind!r}; known: "
            f"{sorted(PEAKS)}. Add a row with its source to "
            "benchmarks/harness/peaks.py") from None
