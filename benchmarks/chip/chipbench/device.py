"""Device check and the table of published peaks, keyed by ``device_kind``."""
from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for", "device_info", "require_chips"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float     # HBM bandwidth of one chip
    hbm_bytes: int             # HBM capacity of one chip
    source: str


# A device missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 2**30,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GiB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(jax, chips: int) -> dict:
    """The platform must be a TPU with at least ``chips`` devices whose kind
    the peaks table knows; anything else raises (no CPU fallback)."""
    info = device_info(jax)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{info['count']}")
    peaks_for(info["kind"])
    return info
