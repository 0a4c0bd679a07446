"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Every roofline term in this repo (``roofline.analysis``, the dry-run
launcher) prices work against one row of :data:`PEAKS`. A device that is
not in the table is an error, never a silent default: a roofline share
computed against another chip's peaks is not a measurement.

Source — Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect per chip over 4 links.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float       # FLOP/s, bf16 (and the price of f32 MXU work)
    int8_ops: float         # OP/s, int8 x int8 -> int32 on the MXU
    hbm_bytes_per_s: float  # HBM bandwidth
    hbm_bytes: int          # HBM capacity
    ici_bytes_per_s: float  # one inter-chip link


PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 1024**3,
        ici_bytes_per_s=1600e9 / 8 / 4,    # 1,600 Gbit/s over 4 links
    ),
}

# the production target of the dry-run meshes (launch/mesh.py)
V5E = PEAKS["TPU v5 lite"]


def device_record() -> dict:
    """The device this process runs on, as every benchmark row names it:
    ``{"platform", "kind", "count"}`` of ``jax.devices()``."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The peaks of ``device_kind`` (as ``jax.devices()[0].device_kind``
    reports it). Unknown kinds raise ``KeyError``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
