#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python chipbench/control.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 [--degrees 1,2,3]
    python chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --fault half_stop

For each seed the cell's pool is made and every entry is run once through
the timed path (``run_gpic`` as the window calls it) and compared with the
reference, as a run compares its jobs. The control is the reference itself
put in the program's place, computed at each precision below the one the
configuration states (for f32 at ``"highest"``: ``"high"``, three bf16
passes, and ``"default"``, one), and compared in the same way. With
``--fault`` a fault from ``FAULTS`` is planted in the program first, and
its rows are the program's with that fault. One JSON line per (side,
seed, entry) on stdout; the largest reading of the program and the
smallest of each control and fault end it.

Each program row also carries how its k-means did against the
reference's: the job's labels' inertia on the reference embedding over
that of the reference k-means (best of its restarts), less one, and the
ARI of the job's labels against the reference's and against the truth.
``--degrees`` reads, on the given seeds, the degree vector of the
program's build kernel called alone (not the timed path: a search for a
number that tells ``high`` from ``highest``) and of the reference at
each precision, against the reference's.

The limits in the configuration sit between the program's largest reading
and the smallest of a control that the output can tell from the program
(PERF.md gives the readings).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from chipbench import data, reference  # noqa: E402
from chipbench import run as harness  # noqa: E402

#: the precisions below each stated precision, nearest first
CONTROL_PRECISIONS = {"highest": ("high", "default")}


def plant_half_stop():
    """The power loop's stop rule fires at half the sweeps it should: the
    loop runs to its own stop, then again from the start to half of it.
    Returns the function that takes the fault out."""
    import jax.numpy as jnp
    from repro.core import power
    loop = power._run_loop_state

    def half(op, state, eps, bound, *args, **kw):
        t = loop(op, state, eps, bound, *args, **kw)[0]
        return loop(op, state, -1.0, jnp.maximum(t // 2, 1), *args, **kw)

    power._run_loop_state = half
    return lambda: setattr(power, "_run_loop_state", loop)


#: faults planted in the program, by name
FAULTS = {"half_stop": plant_half_stop}


def readings(cell, jobs, limits):
    checks, failed, info = harness.compare(cell, jobs, limits)
    return {name: value for name, (value, _limit) in checks.items()}, info


def inertia(z, labels) -> float:
    return float(sum(((z[labels == c] - z[labels == c].mean()) ** 2).sum()
                     for c in np.unique(labels)))


def kmeans_readings(cell, job) -> dict:
    """How the job's k-means did against the reference k-means on the
    reference embedding after as many sweeps."""
    snap, _n_own = cell.reference(job.entry, job.n_iter)
    z = reference.standardize(snap)
    ref = reference.kmeans(z, cell.k)
    return {"kmeans_excess": inertia(z, job.labels) / inertia(z, ref) - 1,
            "ari_reference": data.adjusted_rand_index(ref, job.labels),
            "ari_truth": data.adjusted_rand_index(cell.pool[job.entry][1],
                                                  job.labels)}


def program_side(cell, seed, side="program"):
    out = []
    for entry in range(len(cell.pool)):
        job = cell.run_one(entry)
        if job.error:
            raise RuntimeError(f"seed {seed} entry {entry}: {job.error}")
        got, _info = readings(cell, [job], cell.config["limits"])
        _snap, n_own = cell.reference(job.entry, job.n_iter)
        out.append(dict(side=side, seed=seed, entry=entry,
                        n_iter=job.n_iter, seconds=job.seconds,
                        n_iter_reference=n_own, **got,
                        **kmeans_readings(cell, job)))
    return out


def degree_side(jax, cell, seed, precisions):
    """Degree vectors against the reference's: the program's build kernel
    called alone, and the reference at each precision."""
    import jax.numpy as jnp
    from repro.kernels import ops
    config, name = cell.config, cell.traffic["dataset"]
    sigma = config["sigma"][name]
    build = jax.jit(lambda x: ops.affinity_and_degree(
        x, kind=config["affinity"], sigma=sigma)[1])

    def degrees(x, precision):
        return reference.power_embedding(
            x, sigma=sigma, eps=0.0, max_iter=1, stop_at=1,
            chips=cell.chips, precision=precision)[0]

    def gaps(d, ref):
        return {"deg_err": float(np.abs(d - ref).max() / np.abs(ref).max()),
                "deg_rel_max": float(np.max(np.abs(d - ref) / ref))}

    out = []
    for entry, (x, _y) in enumerate(cell.pool):
        ref = degrees(x, "highest")
        got = np.asarray(build(jnp.asarray(x)))[:len(ref)]
        out.append(dict(side="degrees", who="program_build", seed=seed,
                        entry=entry, **gaps(got, ref)))
        for precision in precisions:
            out.append(dict(side="degrees", who="reference_" + precision,
                            seed=seed, entry=entry,
                            **gaps(degrees(x, precision), ref)))
    return out


def control_side(cell, seed, precision):
    config, name = cell.config, cell.traffic["dataset"]
    out = []
    for entry, (x, _y) in enumerate(cell.pool):
        labels, v, n_iter = reference.cluster(
            x, cell.k, sigma=config["sigma"][name],
            eps=config["eps_scale"] / x.shape[0],
            max_iter=config["max_iter"], chips=cell.chips,
            precision=precision)
        job = harness.Job(entry, 0.0, labels, v, n_iter)
        got, _info = readings(cell, [job], config["limits"])
        out.append(dict(side="control", precision=precision, seed=seed,
                        entry=entry, n_iter=n_iter,
                        n_iter_reference=cell.reference(entry, n_iter)[1],
                        **got))
    return out


def main(argv=None, *, check_device=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--precisions", default="",
                    help="control precisions (default: all below the "
                         "stated one)")
    ap.add_argument("--fault", choices=sorted(FAULTS),
                    help="plant this fault in the program; no control")
    ap.add_argument("--degrees", default="",
                    help="seeds on which to read the degree vectors")
    args = ap.parse_args(argv)
    cell_def, config, traffic, _e2e, _pl = harness.load_cell(args.workload)
    jax = harness.setup_jax()
    devices = (check_device or harness.check_device)(
        jax, int(cell_def["chips"]))
    precisions = ([p for p in args.precisions.split(",") if p]
                  or CONTROL_PRECISIONS[config["precision"]])
    undo = FAULTS[args.fault]() if args.fault else None
    rows = []
    seeds = [int(s) for s in args.seeds.split(",") if s]
    try:
        for seed in seeds:
            rows += one_seed(jax, args, config, traffic, devices, seed,
                             precisions)
    finally:
        if undo:
            undo()
    print(json.dumps(summary(rows)))
    return 0


def one_seed(jax, args, config, traffic, devices, seed, precisions):
    cell = harness.build_cell(jax, config, traffic, seed, devices)
    t0 = time.perf_counter()
    if args.fault:
        rows = program_side(cell, seed, side="fault_" + args.fault)
    else:
        rows = program_side(cell, seed)
        if str(seed) in args.control_seeds.split(","):
            for precision in precisions:
                rows += control_side(cell, seed, precision)
        if str(seed) in args.degrees.split(","):
            rows += degree_side(jax, cell, seed, precisions)
    for row in rows:
        print(json.dumps(row), flush=True)
    print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    return rows


#: the numbers each kind of row is summed up by
READINGS = ("emb_err", "n_iter_err", "label_err")


def summary(rows) -> dict:
    """The program's largest reading of each number, and the smallest of
    each control and fault."""
    out = {"largest_program": {}, "smallest": {}}
    for row in rows:
        if row["side"] == "degrees":
            continue
        if row["side"] == "program":
            side, fold = out["largest_program"], max
        else:
            side = out["smallest"].setdefault(
                row["side"] + "_" + row.get("precision", ""), {})
            fold = min
        for name in READINGS:
            side[name] = fold(side.get(name, row[name]), row[name])
    return out


if __name__ == "__main__":
    sys.exit(main())
