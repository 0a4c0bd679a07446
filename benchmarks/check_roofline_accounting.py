"""Bench-smoke guard: BENCH_throughput.json roofline rows must be priced
by the roofline extractor + analytic megakernel model (DESIGN.md §11) —
mirroring the §9 measured-bytes guard (check_bytes_accounting.py) and the
§10 power guard (check_power_accounting.py).

Three layers of defence:

1. Schema: every per-config sweep row carries a ``roofline`` record with
   ``source == "cost_point+megakernel_cost"`` and a full
   ``RooflineTerms.as_dict()`` under ``model`` (no hand-typed occupancy
   numbers can sneak into the artifact), the pick row names a candidate
   that exists, and the fused-vs-staged row is ``source == "measured-wall"``.
2. Claims: the fused-vs-staged speedup in the artifact is >= 1.5x and its
   stored walls reproduce the stored ratio; the ragged tier delta rows
   satisfy the FLOPs/bytes cuts the bench asserts (>= 3.5x / >= 2.0x).
3. Live re-derivation: ``megakernel_cost`` + ``RooflineTerms`` are re-run
   here at every block shape the artifact reports and compared field by
   field — if someone forks the analytic model away from what the sweep
   recorded (or edits the JSON by hand), this breaks loudly. The ragged
   tier delta is re-derived the same way.

Run after ``benchmarks/run.py`` (needs src and the repo root on the
path): ``PYTHONPATH=src:. python benchmarks/check_roofline_accounting.py``.
"""

import json
import sys

SWEEP_SOURCE = "cost_point+megakernel_cost"


def main(path: str = "BENCH_throughput.json") -> None:
    with open(path) as f:
        results = json.load(f)
    rf = next(v for k, v in results.items() if k.startswith("roofline"))
    rows = {r["name"]: r for r in rf if "name" in r}

    sweep = {n: r for n, r in rows.items()
             if n.startswith("roofline_megakernel_")}
    assert sweep, "no roofline_megakernel_* sweep rows in the artifact"

    # --- layer 1: schema ---------------------------------------------------
    for name, row in sweep.items():
        rec = row.get("roofline")
        assert isinstance(rec, dict), f"{name}: no roofline record"
        assert rec.get("source") == SWEEP_SOURCE, (
            f"{name}: not priced by the extractor+model "
            f"(source={rec.get('source')!r})"
        )
        for key in ("block", "xla", "model"):
            assert key in rec, f"{name}: roofline record missing {key!r}"
        assert "mxu_occupancy" in rec["model"], (
            f"{name}: model record has no mxu_occupancy"
        )

    pick = rows["roofline_block_pick"]["roofline"]
    picked = f"roofline_megakernel_r{pick['block'][0]}" \
             f"_m{pick['block'][1]}_k{pick['block'][2]}"
    assert picked in sweep, f"pick {picked} names no sweep row"
    best_occ = max(r["roofline"]["model"]["mxu_occupancy"]
                   for r in sweep.values())
    assert sweep[picked]["roofline"]["model"]["mxu_occupancy"] == best_occ, (
        f"pick {picked} is not the max-occupancy candidate"
    )

    vs = rows["roofline_fused_vs_staged_af0.25"]["roofline"]
    assert vs.get("source") == "measured-wall"

    # --- layer 2: claims ---------------------------------------------------
    ratio = vs["t_staged_us"] / vs["t_fused_us"]
    assert abs(ratio - vs["speedup"]) < 1e-9, (
        f"stored speedup {vs['speedup']} != stored walls ratio {ratio}"
    )
    assert vs["speedup"] >= 1.5, (
        f"artifact fused-vs-staged speedup {vs['speedup']:.2f}x < 1.5x"
    )

    tier_name = next(n for n in rows if n.startswith("roofline_ragged_tier"))
    tier = rows[tier_name]["roofline"]
    assert tier["source"] == "megakernel_cost"
    flops_ratio = tier["flops_full"] / tier["flops_tier"]
    bytes_ratio = tier["bytes_full"] / tier["bytes_tier"]
    assert flops_ratio >= 3.5, f"ragged FLOPs cut only {flops_ratio:.2f}x"
    assert bytes_ratio >= 2.0, f"ragged bytes cut only {bytes_ratio:.2f}x"

    # --- layer 3: live re-derivation --------------------------------------
    from benchmarks.bench_roofline import TIER_FRACTION, _operating_point
    from repro.roofline.analysis import RooflineTerms, megakernel_cost

    cfg, _, _, _, _, _, k, d = _operating_point()
    n2, m = cfg.patch.pixels_per_patch, cfg.patch.n_vectors
    batch = 4
    for name, row in sweep.items():
        br, bm, bk = row["roofline"]["block"]
        model = megakernel_cost([k] * batch, k, n2, m, d=d,
                                block_r=br, block_m=bm, block_k=bk)
        live = RooflineTerms(
            flops_per_chip=model["flops"], bytes_per_chip=model["bytes"],
            coll_bytes_per_chip=0.0,
            int8_flops_per_chip=model["int8_flops"]).as_dict()
        art = row["roofline"]["model"]
        for key, val in live.items():
            got = art.get(key)
            ok = (got == val) if isinstance(val, str) \
                else abs(got - val) < 1e-9 * max(1.0, abs(val))
            assert ok, (
                f"{name}.{key}: artifact {got!r} != live model {val!r} — "
                f"the analytic roofline model drifted from the artifact"
            )

    br, bm, bk = tier["block"]
    k_eff = max(1, int(round(k * TIER_FRACTION)))
    c_full = megakernel_cost([k] * batch, k, n2, m, d=d,
                             block_r=br, block_m=bm, block_k=bk)
    c_tier = megakernel_cost([k_eff] * batch, k, n2, m, d=d,
                             block_r=br, block_m=bm, block_k=bk)
    for key, have in (("flops_full", c_full["flops"]),
                      ("flops_tier", c_tier["flops"]),
                      ("bytes_full", c_full["bytes"]),
                      ("bytes_tier", c_tier["bytes"])):
        assert abs(tier[key] - have) < 1e-9 * max(1.0, abs(have)), (
            f"ragged delta {key}: artifact {tier[key]} != live {have}"
        )

    print(f"roofline accounting OK: {len(sweep)} modeled sweep rows, pick "
          f"{picked} (occ {best_occ:.3f}) live == artifact, fused vs staged "
          f"{vs['speedup']:.2f}x >= 1.5x, ragged tier cut "
          f"{flops_ratio:.2f}x FLOPs / {bytes_ratio:.2f}x bytes")


if __name__ == "__main__":
    main(*sys.argv[1:])
