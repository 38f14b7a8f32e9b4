"""Per-backend peak-FLOPs table for MFU/HFU denominators.

The efficiency ledger (``obs/ledger.py``) and ``bench.py`` divide
analytically counted model FLOPs by a *claimed hardware peak* to get an
MFU-style ratio.  The table below is deliberately small and honest about
provenance:

- TPU entries are vendor datasheet numbers (bf16, per chip; Google Cloud
  TPU documentation, e.g. "TPU v5e": 197 TFLOP/s).
- The CPU entry is an order-of-magnitude **estimate** (a few AVX2 cores
  at f32), flagged ``estimated=True`` and labeled in every surface that
  prints it.  CPU MFU is only meaningful as a *relative* cross-run
  signal on the same host, never as an absolute utilization claim.
- Any other device - an accelerator whose ``device_kind`` is not in the
  table - has NO peak: ``peak_flops_per_device`` is None and every
  consumer reports no MFU.  Pricing an unknown chip at some default
  would print a utilization that means nothing; add the chip's datasheet
  line here instead.
"""

from __future__ import annotations

from typing import Optional

# bf16 (TPU) / f32 (CPU) peak FLOP/s per device.  Keys are lowercase
# substrings matched against ``device_kind`` (see ``peak_flops``).
PEAK_FLOPS_TABLE: dict[str, float] = {
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v4": 275e12,
    "tpu v3": 123e12,
    "tpu v2": 45e12,
}

# Estimated: ~8 cores x ~3 GHz x 2 FMA ports x 8 f32 lanes.  Labeled
# wherever it is surfaced; see module docstring.
CPU_PEAK_FLOPS_ESTIMATE = 4e11


def peak_flops(backend: Optional[str] = None,
               device_kind: Optional[str] = None) -> dict:
    """Claimed per-device peak FLOP/s for a backend/device pair.

    Returns ``{"peak_flops_per_device", "device", "estimated"}``:
    the datasheet number for a ``device_kind`` in the table, the flagged
    estimate for the CPU (also when neither argument is given - offline
    consumers without jax), and ``peak_flops_per_device=None`` for any
    other device.
    """
    kind = (device_kind or "").lower()
    for key, peak in PEAK_FLOPS_TABLE.items():
        if key in kind:
            return {
                "peak_flops_per_device": peak,
                "device": device_kind,
                "estimated": False,
            }
    if backend == "cpu" or (backend is None and kind in ("", "cpu")):
        return {
            "peak_flops_per_device": CPU_PEAK_FLOPS_ESTIMATE,
            "device": device_kind or "cpu",
            "estimated": True,
        }
    return {
        "peak_flops_per_device": None,
        "device": device_kind or backend,
        "estimated": False,
    }


def local_peak_flops() -> dict:
    """``peak_flops`` for the ambient jax backend (total across devices).

    Lazy-imports jax and degrades to the labeled CPU estimate when jax
    is unavailable, so offline CLI consumers never fail here.
    """
    backend = device_kind = None
    count = 1
    try:  # pragma: no cover - exercised only when jax import fails
        import jax

        backend = jax.default_backend()
        devices = jax.devices()
        count = len(devices)
        device_kind = devices[0].device_kind
    except Exception:
        pass
    info = peak_flops(backend, device_kind)
    info["device_count"] = count
    per_device = info["peak_flops_per_device"]
    info["peak_flops_total"] = (
        None if per_device is None else per_device * count)
    info["backend"] = backend or "cpu"
    return info
