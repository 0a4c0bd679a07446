"""Benchmark harness — one module per paper table/figure (DESIGN.md §8).

Prints ``name,us_per_call,derived`` CSV and writes ``BENCH_throughput.json``
(all rows, keyed by module) so successive PRs accumulate a perf trajectory.
``--quick`` swaps the full accuracy study (bench_accuracy trains 10 small
models and dominates wall time) for its smoke arm: one short train plus
the served-wire evals (dense oracle vs int8 code wire vs 1-bit sign wire).
"""

import argparse
import json
import sys
import traceback
import types


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json-out", default="BENCH_throughput.json")
    args = ap.parse_args()

    from benchmarks import (
        bench_fleet, bench_kernels, bench_leakage, bench_power,
        bench_roofline, bench_rollout, bench_throughput,
    )

    modules = [
        ("leakage(§2.1.2)", bench_leakage),
        ("power+area(Table1,§2.1.3)", bench_power),
        ("throughput(Fig.3,§2.1.4)", bench_throughput),
        ("kernels", bench_kernels),
        ("roofline(§11)", bench_roofline),
        ("fleet(§12)", bench_fleet),
        ("rollout(§15)", bench_rollout),
    ]
    from benchmarks import bench_accuracy

    if args.quick:
        # smoke arm: one short train + served-wire evals (code/sign), so
        # the accuracy seams stay covered in the bench-smoke CI lane
        modules.append((
            "accuracy-smoke(§13)",
            types.SimpleNamespace(run=bench_accuracy.run_quick),
        ))
    else:
        modules.append(("accuracy(§1,§2.1.3,§2.1.5,Fig.4)", bench_accuracy))

    from repro.roofline.peaks import device_record

    # rows measured in this process name its device; the CPU-pinned
    # child harnesses (fleet, rollout, multistream) stamp their own
    here = device_record()
    print(f"# device {json.dumps(here)}")
    print("name,us_per_call,derived")
    failures = 0
    results: dict[str, list[dict]] = {}
    for label, mod in modules:
        try:
            rows = mod.run()
            for row in rows:
                row.setdefault("device", here)
            results[label] = rows
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
        except Exception as e:
            failures += 1
            # a module may attach the rows it collected before failing
            # (bench_throughput does): keep them in the artifact so one
            # failed sweep doesn't erase the others' perf trajectory
            kept = list(getattr(e, "rows", []))
            for row in kept:
                print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
            results[label] = kept + [
                {"name": label, "error": f"{type(e).__name__}: {e}"}]
            print(f"{label},FAIL,{type(e).__name__}: {e}", file=sys.stderr)
            traceback.print_exc()
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.json_out}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
