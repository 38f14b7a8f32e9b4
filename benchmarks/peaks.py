"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default: a utilisation against a guessed peak is a made-up number."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB HBM2e at 819 GB/s.  f32 matmuls at JAX's default precision
    # take one bf16 pass on the MXU, so the bf16 peak is the yardstick
    # for the f32 configurations too.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmarks/peaks.py has no row for device_kind "
            f"{device_kind!r}: add its published peaks (with the source) "
            "before measuring on it"
        ) from None
