"""The yardstick's arithmetic: published chip peaks, and the operations
and bytes each kernel needs, from shapes alone. (The operations of a
whole served frame depend on the backend: ``frame_ops`` of
``systems/<name>.py``.)

Peaks: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s. A device
kind that is not in the table is an error, never a default. Float32
matmul work is priced at the bf16 peak (the MXU's fastest float rate),
so a float32 kernel's share can only be understated, never overstated.

Roofline counts are the least the algorithm needs: each operand read
once, each result written once, no padding. (The ragged kernel's own
traffic, weights streamed once per row bank, is higher; see
``repro.roofline.analysis.megakernel_cost``.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 1024**3},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None


def roofline_s(flops: float, bytes_: float, peak_flops: float,
               pk: dict) -> float:
    """The least time: the larger of compute and memory time."""
    return max(flops / peak_flops, bytes_ / pk["hbm_bytes_per_s"])


def projection_min(rows: int, n2: int, m: int, out_rows: int) -> dict:
    """Ragged projection with the fused ADC: ``rows`` real patch rows of
    ``n2`` float32 pixels against (m, n2) float32 weights, int8 codes out
    for ``out_rows`` rows."""
    return {"flops": 2.0 * rows * n2 * m,
            "bytes": rows * n2 * 4.0 + m * n2 * 4.0 + m * 4.0
            + out_rows * m * 1.0}


def w8a8_min(rows: int, kdim: int, n: int) -> dict:
    """int8 codes (rows, kdim) @ int8 weights (kdim, n), float32 out."""
    return {"ops": 2.0 * rows * kdim * n,
            "bytes": rows * kdim + kdim * n + n * 4.0 + rows * 4.0
            + rows * n * 4.0}


def delta_attention_min(q_rows: int, k: int, d_model: int,
                        n_heads: int) -> dict:
    """Stale-query attention of one slot and layer: ``q_rows`` float32
    queries against ``k`` keys and values, all heads."""
    dh = d_model // n_heads
    return {"flops": n_heads * 2.0 * 2.0 * q_rows * k * dh,
            "bytes": (2.0 * q_rows + 2.0 * k) * n_heads * dh * 4.0
            + k * 4.0}
