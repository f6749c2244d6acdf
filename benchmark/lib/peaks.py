"""The table of peaks, keyed by the ``device_kind`` jax reports. A kind
that is not here is an error: a share of the wrong peak is worse than
none. (Copied from ``bench.py`` ``PEAK_TFLOPS`` / ``device_stamp``, with
bandwidth and memory added; the original is listed in PERF.md for a
later PR to delete.)"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. A v5e chip
    # reports device_kind "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to benchmark/lib/peaks.py with its "
            "source before reporting a share of it") from None


def device_stamp() -> dict:
    """The device as jax reports it, for the result line."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def memory_peak_bytes(devices=None) -> int:
    """``peak_bytes_in_use`` on the fullest of ``devices``."""
    import jax
    peak = 0
    for d in devices or jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
