"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # quick set
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
    PYTHONPATH=src python -m benchmarks.run --json     # + BENCH_PR10.json

``--json [PATH]`` additionally writes a machine-readable perf snapshot
(us/call per job row plus the engine sweep-count model) for CI diffing.
"""
from __future__ import annotations

import argparse
import json
import sys


def _rows_to_records(rows):
    recs = []
    for row in rows:
        name, us, *derived = row.split(",", 2)
        recs.append({
            "name": name,
            "us_per_call": float(us),
            "derived": derived[0] if derived else "",
        })
    return recs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table1,table2,fig3,exp2,"
                         "roofline,multivec,distributed,quality,affinity,"
                         "robustness")
    ap.add_argument("--json", nargs="?", const="BENCH_PR10.json", default=None,
                    metavar="PATH",
                    help="write a JSON perf snapshot (default BENCH_PR10.json)")
    args = ap.parse_args()

    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    from . import (bench_affinity, bench_distributed, bench_exp2, bench_fig3,
                   bench_multivec, bench_quality, bench_robustness,
                   bench_table1, bench_table2, roofline)

    jobs = {
        "table1": lambda: bench_table1.run(
            sizes=(1000, 2000, 4000, 8000) if args.full else (1000, 2000)),
        "table2": lambda: bench_table2.run(
            sizes=(1000, 2000, 4000, 8000) if args.full else (1000, 2000)),
        "fig3": lambda: bench_fig3.run(),
        "exp2": lambda: bench_exp2.run(
            n=45_000 if args.full else 9_000,
            repeats=10 if args.full else 2,
            fractions=((0.002, 0.005, 0.01, 0.02, 0.05, 0.1) if args.full
                       else (0.01, 0.05, 0.2))),
        "roofline": roofline.run,
        "multivec": lambda: bench_multivec.run(
            n=2048 if args.full else 1024),
        "distributed": lambda: bench_distributed.run(
            n=2048 if args.full else 1024),
        # the quality section: per-dataset ARI for every embedding mode +
        # per-sweep QR cost at r in {1, 4, 8} (tracked across snapshots)
        "quality": lambda: bench_quality.run(
            n=960 if args.full else 480,
            qr_n=2048 if args.full else 1024),
        # the affinity-graph subsystem: two-pass build + sweep cost dense
        # vs truncated, the two_moons kNN acceptance, and the subspace
        # residual stopping rule (reduction asserted on every run)
        "affinity": lambda: bench_affinity.run(
            n=2048 if args.full else 1024,
            moons_n=960 if args.full else 480),
        # the robustness subsystem: divergence-latch overhead vs the
        # latch-free loop (budget asserted; fixed n — at 4096 the 5 s
        # interpret-mode walls drown the sub-1% effect in timer noise),
        # front-door validation cost, component-probe cost, and the fault
        # matrix (every degenerate input must resolve to its contracted
        # outcome — asserted)
        "robustness": lambda: bench_robustness.run(n=2048),
    }
    selected = (args.only.split(",") if args.only else list(jobs))

    snapshot = {"jobs": {}, "sweep_model": []}
    print("name,us_per_call,derived")
    for name in selected:
        try:
            rows = jobs[name]()
            for row in rows:
                print(row, flush=True)
            if args.json:
                # jobs["distributed"] is the per-path sweep-timing section
                # tracked across PR snapshots
                snapshot["jobs"][name] = _rows_to_records(rows)
        except Exception as e:  # keep the harness running
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", file=sys.stderr)
            raise

    if args.json:
        n = 2048 if args.full else 1024
        for mode in ("seed_pervec", "engine_explicit", "engine_streaming"):
            for r in (1, 4):
                snapshot["sweep_model"].append(roofline.sweep_model(n, r, mode))
        with open(args.json, "w") as f:
            json.dump(snapshot, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
