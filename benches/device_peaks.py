"""Published per-chip peaks, keyed by jax's `device_kind`.

The ONE table every bench that divides by a peak reads (roofline shares,
memory ceilings).  A device that is not in it is an error, never a
default: a share of the wrong chip's peak is a wrong number under a
right-looking name.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (per chip); jax reports the
    # chip as device_kind "TPU v5 lite"
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16 * 1024**3,
        "hbm_bps": 819e9,
        "ici_bps": 1600e9 / 8,
    },
}


def peaks_for(device) -> dict:
    """The peak table row for a jax device; raises on an unlisted kind."""
    kind = device.device_kind
    if kind not in PEAKS:
        raise SystemExit(
            f"no published peaks for device_kind={kind!r} "
            f"(platform={device.platform}): add a sourced row to "
            f"benches/device_peaks.py — known kinds: {sorted(PEAKS)}")
    return PEAKS[kind]
