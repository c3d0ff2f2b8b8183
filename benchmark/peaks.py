"""Published per-chip peaks, keyed by jax's `device_kind`.

The benchmark's own copy of the peak table (the program's is
`benches/device_peaks.py`; the yardstick must not move when the program
does).  A device that is not listed is an error, never a default: a share
of the wrong chip's peak is a wrong number under a right-looking name.
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


def peaks_for(device_kind: str) -> dict:
    """The table row for a `device_kind`; raises KeyError on an unlisted one."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}: add a sourced "
            f"row to benchmark/peaks.py (known kinds: {sorted(PEAKS)})")
    return PEAKS[device_kind]
