"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch, shape, mesh), in seconds, priced against the TPU
v5e row of the device peaks table (:mod:`repro.roofline.peaks`):

    compute    = float_FLOPs / bf16_peak + int8_OPs / int8_peak
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = collective_bytes_per_chip / ICI_bw

Sources: ``compiled.cost_analysis()`` (per-partition flops / bytes
accessed) and the partitioned HLO text for collective operand bytes.

XLA's cost analysis counts a ``while`` (lax.scan) body ONCE regardless of
trip count, so per-layer costs of scanned stacks are recovered by
two-point extrapolation: lower the model UNROLLED at 1x and 2x the block
pattern, take the difference as the per-repeat cost, and extrapolate to
the full depth. This is exact for homogeneous stacks (the difference
cancels embed/head/optimizer overheads) and is validated against the
analytic MODEL_FLOPS = 6·N·D in the tests.
"""

from __future__ import annotations

import dataclasses
import re

from repro.roofline.peaks import V5E

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9_\[\]\{\},\. ]+?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.I,
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _line_result_bytes(line: str) -> int:
    """Bytes of the result shape(s) on an HLO op line ('%x = TYPE op(...').

    Results may be tuple-shaped — '%x = (f32[8,128], u32[]) all-reduce-start(…'
    — where everything before the first '(' is empty; the result shapes
    then live inside the leading parenthesized group, which must be kept
    (only the operand list after the op name is excluded)."""
    lhs = line.split("=", 1)[1] if "=" in line else line
    lhs = lhs.lstrip()
    if lhs.startswith("("):
        # tuple result: scan up to its closing paren, not the first '('
        close = lhs.find(")")
        head = lhs[: close + 1] if close != -1 else lhs
    else:
        head = lhs.split("(", 1)[0]
    total = 0
    for dt, dims in _SHAPE_RE.findall(head):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective kind (result-shape sized)."""
    out: dict[str, int] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1).lower()
        if "-done" in line.split("(")[0]:
            continue  # avoid double count of start/done pairs
        b = _line_result_bytes(line)
        out[kind] = out.get(kind, 0) + b
        count[kind] = count.get(kind, 0) + 1
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["counts"] = count
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    # the part of flops_per_chip the MXU runs as int8 x int8 (w8a8 paths):
    # priced at the int8 peak, the rest at the bf16 peak
    int8_flops_per_chip: float = 0.0

    @property
    def t_compute(self) -> float:
        float_flops = self.flops_per_chip - self.int8_flops_per_chip
        return (float_flops / V5E.bf16_flops
                + self.int8_flops_per_chip / V5E.int8_ops)

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / V5E.hbm_bytes_per_s

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / V5E.ici_bytes_per_s

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mxu_occupancy(self) -> float:
        """Fraction of the bound time the MXU is doing useful math:
        t_compute / t_bound — 1.0 when compute-bound, < 1 when memory or
        collective traffic stalls the systolic array. The block-shape
        sweep (benchmarks/bench_roofline.py) maximizes this."""
        t = self.t_bound
        return self.t_compute / t if t > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "coll_bytes_per_chip": self.coll_bytes_per_chip,
            "int8_flops_per_chip": self.int8_flops_per_chip,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "mxu_occupancy": self.mxu_occupancy,
        }


def extrapolate(point1: dict, point2: dict, n_rep1: int, n_rep2: int,
                n_rep_full: int) -> RooflineTerms:
    """Two-point linear extrapolation of per-chip costs to full depth."""
    def extr(key):
        v1, v2 = point1[key], point2[key]
        slope = (v2 - v1) / max(n_rep2 - n_rep1, 1)
        return v1 + slope * (n_rep_full - n_rep1)

    return RooflineTerms(
        flops_per_chip=extr("flops"),
        bytes_per_chip=extr("bytes"),
        coll_bytes_per_chip=extr("coll_bytes"),
    )


def cost_point(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total"]),
        "coll_detail": {k: v for k, v in coll.items() if k not in ("total",)},
    }


def megakernel_cost(
    row_counts,
    k: int,
    n2: int,
    m: int,
    d: int | None = None,
    block_r: int = 8,
    block_m: int = 128,
    block_k: int = 256,
    out_bytes: int = 1,
) -> dict:
    """Analytic (flops, bytes) model of the ragged frontend megakernel
    (DESIGN.md §11) at given per-slot ``row_counts``.

    XLA's static cost analysis prices every grid step, so runtime
    raggedness — banks whose MXU work is skipped by ``pl.when`` and whose
    DMAs the pipeliner elides on unchanged block indices — is invisible to
    :func:`cost_point`. This model prices what the kernel ACTUALLY does: a
    row bank of ``block_r`` slots only computes/streams when its first row
    position is below its slot's count, so FLOPs and bytes scale with
    ``sum(ceil(count/block_r))`` active banks, not with slots·k. Output
    writes cover every bank (inactive banks write zeros — the defined
    shed-row payload). ``d`` prices the fused embed stage (codes @ W8)
    on top; ``d=None`` is the ragged projection alone with ``out_bytes``
    per emitted element (1 for the int8 code wire). Same keys as
    :func:`cost_point` so :class:`RooflineTerms` consumes either, plus
    ``int8_flops``: the embed stage's share of ``flops``, which runs
    int8 x int8 on the MXU.
    """
    k_pad = -(-n2 // block_k) * block_k
    m_pad = -(-m // block_m) * block_m
    n_banks = -(-k // block_r)
    counts = [max(0, min(int(c), k)) for c in row_counts]
    active_banks = sum(-(-c // block_r) for c in counts)
    total_banks = len(counts) * n_banks

    flops = active_banks * 2.0 * block_r * k_pad * m_pad
    int8_flops = 0.0
    bytes_ = active_banks * block_r * k_pad * 4.0       # gathered patch rows
    bytes_ += active_banks * k_pad * m_pad * 4.0        # weight stream/bank
    if d is None:
        bytes_ += total_banks * block_r * m_pad * float(out_bytes)
    else:
        d_pad = -(-d // 128) * 128
        int8_flops = active_banks * 2.0 * block_r * m_pad * d_pad
        flops += int8_flops
        bytes_ += m_pad * d_pad * 1.0 + d_pad * 4.0     # embed w8 + scales
        bytes_ += total_banks * block_r * d_pad * 4.0   # f32 embed output
    return {
        "flops": flops,
        "int8_flops": int8_flops,
        "bytes": bytes_,
        "coll_bytes": 0.0,
        "detail": {"active_banks": active_banks, "total_banks": total_banks},
    }


def delta_attention_cost(
    j: int,
    k: int,
    d_model: int,
    n_heads: int,
    block_q: int = 8,
    lane: int = 128,
) -> dict:
    """Analytic (flops, bytes) model of the ragged stale-Q attention
    kernel (DESIGN.md §14) for ONE (slot, layer): ``j`` stale query rows
    against ``k`` cached keys.

    Mirrors :func:`megakernel_cost`'s reasoning: ``pl.when`` + clamped
    index_maps mean only ``ceil(j/block_q)`` query banks compute and
    stream, each paying the FULL key/value block (attention is all-to-
    all on the key side — that is the kernel's irreducible term), so
    cost scales with the stale prefix, not with k². Head dim is
    lane-padded exactly as the kernel pads it. ``time_s`` is the
    roofline bound (max of compute/memory), the quantity
    :func:`repro.kernels.vit_delta_attention.pick_block_q` minimizes.
    """
    dh = max(d_model // n_heads, 1)
    dh_p = -(-dh // lane) * lane
    k_pad = -(-k // block_q) * block_q
    active = -(-max(min(j, k), 0) // block_q)
    total = -(-k // block_q)

    # per active bank, per head: scores (bq x k_pad x dh_p) + mix back
    flops = active * n_heads * 2.0 * (2.0 * block_q * k_pad * dh_p)
    bytes_ = active * n_heads * block_q * dh_p * 4.0          # Q banks
    bytes_ += (n_heads * 2.0 * k_pad * dh_p * 4.0             # K + V
               * (1.0 if active > 0 else 0.0))
    bytes_ += k_pad * 4.0 * (1.0 if active > 0 else 0.0)      # key mask
    bytes_ += total * n_heads * block_q * dh_p * 4.0          # output banks
    t = RooflineTerms(flops, bytes_, 0.0)
    return {
        "flops": flops,
        "bytes": bytes_,
        "coll_bytes": 0.0,
        "time_s": t.t_bound,
        "detail": {"active_banks": active, "total_banks": total,
                   "bottleneck": t.bottleneck},
    }


def delta_backend_cost(
    j_embed: float,
    j_qkv,
    q_attn,
    k: int,
    m: int,
    d_model: int,
    n_heads: int,
    d_ff: int,
    n_classes: int,
    block_q: int = 8,
) -> dict:
    """Analytic per-frame cost of the whole delta-gated backend
    (DESIGN.md §14): embed + per-layer QKV/attention/MLP + head, at the
    stale populations the gate actually touched (``j_qkv``/``q_attn``
    are per-layer sequences — the same populations
    :func:`repro.core.power.backend_frame_macs` prices in MACs; this
    model adds the roofline bytes so block shapes and speedup claims
    derive from one place). FLOPs = 2·MACs on the row terms; attention
    terms defer to :func:`delta_attention_cost` per layer.
    """
    d = d_model
    flops = 2.0 * j_embed * m * d + 2.0 * float(n_classes * d)
    bytes_ = j_embed * (m * 1.0 + d * 4.0) + m * d * 1.0
    detail = {"layers": []}
    for j_l, q_l in zip(j_qkv, q_attn):
        attn = delta_attention_cost(
            int(q_l), k, d_model, n_heads, block_q=block_q)
        lf = 2.0 * (j_l * 3.0 * d * d + q_l * (d * d + 2.0 * d * d_ff))
        lb = (j_l + q_l) * d * 4.0 * 2.0 + (3.0 * d * d + 2.0 * d * d_ff) * 4.0
        flops += lf + attn["flops"]
        bytes_ += lb + attn["bytes"]
        detail["layers"].append({"row_flops": lf, "attn": attn["detail"]})
    t = RooflineTerms(flops, bytes_, 0.0)
    return {
        "flops": flops,
        "bytes": bytes_,
        "coll_bytes": 0.0,
        "time_s": t.t_bound,
        "detail": detail,
    }


def model_flops(n_active_params: int, tokens: int, is_train: bool) -> float:
    """MODEL_FLOPS = 6·N·D (train: fwd+bwd) or 2·N·D (inference fwd)."""
    return (6.0 if is_train else 2.0) * n_active_params * tokens
