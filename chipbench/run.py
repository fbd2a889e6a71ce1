#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``chipbench/configs/<config>.json``, the deployment's sizes) under a
traffic mix (``chipbench/traffic/<traffic>.json``, the pool of inputs a
client cycles). Per-layer metrics are read by ``chipbench/metrics/<name>.py``.
Everything is found by name; nothing here knows a cell.

The loop is closed with one client, as a user's pipeline that waits for
labels before it sends the next job. Set-up makes the pool from
``--seed``, places it on the chip, and runs one job, which compiles (or
loads from the compile cache kept in ``chipbench/.jax_cache``). The
window then calls ``run_gpic`` back to back, cycling the pool, each job
timed from the call to its labels on the host, and closes at the end of
the first whole pass over the pool that ends after ``--seconds``.

With ``--trace 0`` the last line of stdout carries the cell's end-to-end
metrics; with ``--trace 1`` a traced pass or more gives its per-layer
metrics. Either way every job of the run is compared with the plain
reference after the window (``chipbench/reference.py``); the numbers
compared and their limits end stderr and the result line.

A run refuses to measure (exit 2, no result) on anything but a TPU, with
fewer chips than the cell asks for, with Pallas in interpret mode, or when
a kernel has fallen back to its jnp reference.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout root, not this directory, goes first on the path: the
# benchmark's modules are imported as ``chipbench.*`` and never shadow a
# standard module (``chipbench/trace.py`` vs the standard ``trace``)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import data, reference  # noqa: E402
from chipbench import trace as traces  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402

BENCH_DIR = os.path.join(ROOT, "chipbench")
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")
JOB_SPAN = "chipbench.job"
#: seconds of device activity the traced passes cover at least
TRACE_SECONDS = 2.0


class Refused(Exception):
    """The run cannot be measured here; nothing is printed on stdout."""


def load_cell(workload: str):
    """(cell, config, traffic, the metrics BENCHMARK.json lists) by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; "
                      f"have {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return (cell, config, traffic, mine(bench["end_to_end"]),
            mine(bench["per_layer"]))


def check_device(jax, chips: int):
    """Refuse anything but compiled kernels on ``chips`` TPU chips."""
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        raise Refused("REPRO_FORCE_INTERPRET is set: the kernels must run "
                      "compiled on the TPU, never in interpret mode")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found platform {devs[0].platform!r} "
                      f"({devs[0].device_kind}); a CPU run is not a chip run")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    from repro.kernels import ops
    if ops._INTERPRET:
        raise Refused("Pallas kernels are in interpret mode on a TPU")
    return devs[:chips]


def check_fallbacks(cell):
    """Refuse a run in which any kernel fell back to its jnp reference."""
    from repro.kernels import ops
    if ops.kernel_fallbacks():
        raise Refused(f"kernel fallbacks: {ops.kernel_fallbacks()}")
    if cell.fallback_notes:
        raise Refused(f"kernel fell back to its reference: "
                      f"{sorted(set(cell.fallback_notes))}")


class CompileClock:
    """Compile stages and persistent-cache hits and misses, from JAX's own
    monitoring events, each with the time it was recorded."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "backend"}
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self, jax):
        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.STAGES:
            self.events.append((time.perf_counter(), self.STAGES[event],
                                duration))

    def _on_event(self, event, **_):
        if event in self.CACHE:
            self.events.append((time.perf_counter(), self.CACHE[event], 0.0))

    def summary(self, lo: float = 0.0, hi: float = math.inf) -> dict:
        """Counts and seconds of each kind of event recorded in [lo, hi]."""
        out: dict = {}
        for at, kind, secs in self.events:
            if lo <= at <= hi:
                out[kind + "_n"] = out.get(kind + "_n", 0) + 1
                if kind in self.STAGES.values():
                    out[kind + "_s"] = out.get(kind + "_s", 0.0) + secs
        return out


def setup_jax():
    """Import JAX with the compile cache at the benchmark's fixed path
    inside the checkout, whatever the environment says, and every
    program cached however fast it compiled. The TPU runtime's logs go
    inside the checkout too, unless the environment places them."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.makedirs(os.environ.setdefault(
        "TPU_LOG_DIR", os.path.join(BENCH_DIR, ".tpu_logs")), exist_ok=True)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def gpic_config(config: dict, traffic: dict, mesh=None):
    import jax.numpy as jnp
    from repro.core import GPICConfig
    name = traffic["dataset"]
    return GPICConfig(
        engine=config["engine"], affinity_kind=config["affinity"],
        sigma=config["sigma"][name], n_vectors=config["n_vectors"][name],
        max_iter=config["max_iter"], eps_scale=config["eps_scale"],
        a_dtype=getattr(jnp, config["a_dtype"]), mesh=mesh)


@dataclass
class Job:
    entry: int                 # index into the pool
    seconds: float             # call to labels on the host
    labels: object = None      # numpy (n,)
    embedding: object = None   # numpy (n,): what k-means clustered
    n_iter: int = 0
    error: str | None = None   # a typed failure of the program


@dataclass
class Cell:
    """What one run drives: the pool on the chip, the program's config and
    the function that runs one job."""
    config: dict
    traffic: dict
    chips: int
    pool: list                 # (x numpy, y numpy) per entry
    placed: list               # x on the chip(s) per entry
    k: int
    run_one: object            # callable(entry) -> Job
    fallback_notes: list = field(default_factory=list)
    #: reference runs by (entry, sweeps): (v after those sweeps, the sweep
    #: at which the reference's own stop rule fired)
    refs: dict = field(default_factory=dict)

    def reference(self, entry: int, sweeps: int):
        """The plain reference on pool entry ``entry``, run at least
        ``sweeps`` sweeps and past its own stop (cached)."""
        if (entry, sweeps) not in self.refs:
            x = self.pool[entry][0]
            name = self.traffic["dataset"]
            _d, snap, _v, n_own = reference.power_embedding(
                x, sigma=self.config["sigma"][name],
                eps=self.config["eps_scale"] / x.shape[0],
                max_iter=self.config["max_iter"], stop_at=sweeps,
                chips=self.chips)
            self.refs[entry, sweeps] = snap, n_own
        return self.refs[entry, sweeps]


def build_cell(jax, config, traffic, seed: int, devices) -> Cell:
    """The pool from ``seed``, placed on the cell's chips, and its job."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import run_gpic
    from repro.core.health import GPICError
    chips = len(devices)
    if config["n_vectors"][traffic["dataset"]] != 1:
        raise ValueError("the label check reads a 1-D embedding "
                         "(n_vectors 1); this cell clusters more columns")
    pool, k = data.make_pool(traffic, config["n"], seed)
    mesh = None
    if chips > 1:
        mesh = jax.make_mesh((chips,), ("data",), devices=devices)
        rows = NamedSharding(mesh, PartitionSpec("data"))
        placed = [jax.device_put(x, rows) for x, _ in pool]
    else:
        placed = [jax.device_put(x, devices[0]) for x, _ in pool]
    cfg = gpic_config(config, traffic, mesh)
    key = jax.random.key(int(np.random.default_rng((seed, 1))
                             .integers(2**31)))
    jax.block_until_ready((placed, key))
    cell = Cell(config, traffic, chips, pool, placed, k, None)

    def run_one(entry: int) -> Job:
        t0 = time.perf_counter()
        try:
            res = run_gpic(placed[entry], k, cfg, key=key)
            labels, emb, n_iter = jax.device_get(
                (res.labels, res.embeddings, res.n_iter))
        except GPICError as e:
            return Job(entry, time.perf_counter() - t0,
                       error=f"{type(e).__name__}: {e}")
        job = Job(entry, time.perf_counter() - t0, np.asarray(labels),
                  np.asarray(emb)[:, 0], int(n_iter))
        cell.fallback_notes.extend(
            n for n in res.health.notes if "kernel_fallback" in n)
        return job

    cell.run_one = run_one
    return cell


def run_passes(cell: Cell, seconds: float, *, at_least: int = 1,
               annotate: bool = False):
    """Jobs cycling the pool until ``seconds`` have passed, closed at the
    end of a whole pass, and at least ``at_least`` passes."""
    import jax
    jobs = []
    t0 = time.perf_counter()
    passes = 0
    while passes < at_least or time.perf_counter() - t0 < seconds:
        for entry in range(len(cell.pool)):
            if annotate:
                with jax.profiler.TraceAnnotation(JOB_SPAN):
                    jobs.append(cell.run_one(entry))
            else:
                jobs.append(cell.run_one(entry))
        passes += 1
    return jobs, time.perf_counter() - t0


def peak_memory(devices) -> int:
    """Peak bytes reserved on the fullest chip: on the TPU runtime this
    counter covers program temporaries (the stored A), which
    ``peak_bytes_in_use`` does not."""
    return max(int(d.memory_stats()["peak_bytes_reserved"])
               for d in devices)


def end_to_end(names, *, setup_s, jobs, window_s, peak_bytes):
    """The cell's end-to-end metrics, from the window's jobs."""
    import numpy as np
    done = [j for j in jobs if j.error is None]
    values = {
        "setup_s": lambda: setup_s,
        "time_to_labels_s": lambda: window_s / max(len(done), 1),
        "time_to_labels_p95_s": lambda: float(
            np.percentile([j.seconds for j in jobs], 95)),
        "peak_hbm_gb": lambda: peak_bytes / 1e9,
    }
    out = {}
    for m in names:
        if m["name"] not in values:
            raise KeyError(f"no end-to-end metric {m['name']!r} in run.py")
        out[m["name"]] = {"value": values[m["name"]](), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# correctness: every job against the plain reference
# ---------------------------------------------------------------------------

def compare(cell: Cell, jobs, limits: dict):
    """Compare every job with the reference run on its input.

    ``emb_err``: the largest gap between the job's embedding and the
    reference's after as many sweeps as the job ran, over the reference's
    largest entry. ``n_iter_err``: the largest gap, in sweeps, between
    where the job's stop rule fired and where the reference's did.
    ``label_err``: points whose label a nearest-centroid assignment of the
    job's own embedding would not give, plus clusters missing
    (``data.labels_out_of_place``): k-means output reads 0, so the limit
    is 0.

    Returns (checks {name: [value, limit]}, jobs that failed, info).
    """
    import numpy as np
    emb_err, n_iter_err, label_err, failed = 0.0, 0, 0, 0
    for job in jobs:
        if job.error is not None:
            failed += 1
            continue
        snap, n_own = cell.reference(job.entry, job.n_iter)
        err = float(np.abs(job.embedding - snap).max() / np.abs(snap).max())
        stop = abs(job.n_iter - n_own)
        moved = data.labels_out_of_place(job.embedding, job.labels, cell.k)
        emb_err, n_iter_err = max(emb_err, err), max(n_iter_err, stop)
        label_err = max(label_err, moved)
        if (err > limits["emb_err"] or stop > limits["n_iter_err"]
                or moved > limits["label_err"]):
            failed += 1
    checks = {"emb_err": [emb_err, limits["emb_err"]],
              "n_iter_err": [n_iter_err, limits["n_iter_err"]],
              "label_err": [label_err, limits["label_err"]]}
    info = {"n_iter": sorted({j.n_iter for j in jobs}),
            "n_iter_reference": sorted({n for _s, n in cell.refs.values()})}
    return checks, failed, info


# ---------------------------------------------------------------------------
# per-layer metrics from the trace
# ---------------------------------------------------------------------------

@dataclass
class TracedRun:
    """What a per-layer metric's reader gets: the traced jobs, the trace,
    the traced window and the chip's peaks."""
    config: dict
    traffic: dict
    chips: int
    n: int                     # points per job
    jobs: list                 # the traced Jobs
    trace: traces.Trace
    lo: float                  # traced window, ns on the trace's clock
    hi: float
    peaks: dict

    @property
    def sweeps(self) -> int:
        return sum(j.n_iter for j in self.jobs)

    def kernel_seconds(self, names) -> list[float]:
        """Device seconds of ops matching ``names`` per chip."""
        return [traces.op_seconds(ops, names, self.lo, self.hi)
                for ops in self.trace.device_ops.values()]


def load_reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(names, run: TracedRun):
    out = {}
    for m in names:
        value = load_reader(m["name"])(run)
        if value is None:
            print(f"chipbench: per-layer metric {m['name']} found nothing "
                  "to read in this run's trace; left out", file=sys.stderr)
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_window(trace: traces.Trace):
    spans = trace.annotations(JOB_SPAN)
    if not spans:
        raise RuntimeError(f"no {JOB_SPAN} spans in the trace")
    return spans[0].start, spans[-1].end


def trace_passes(jax, cell: Cell):
    """One untraced pass (its length sets how many to trace), then whole
    traced passes covering TRACE_SECONDS: (jobs, trace, lo, hi)."""
    jobs, pass_s = run_passes(cell, 0.0)
    passes = max(1, math.ceil(TRACE_SECONDS / pass_s))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        traced, _ = run_passes(cell, 0.0, at_least=passes, annotate=True)
    finally:
        jax.profiler.stop_trace()
    trace = traces.load(TRACE_DIR)
    lo, hi = traced_window(trace)
    return jobs, traced, trace, lo, hi


# ---------------------------------------------------------------------------

def report(result: dict, checks: dict) -> None:
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in checks.items()}
    print(json.dumps(result), flush=True)


def measure(args) -> int:
    cell_def, config, traffic, e2e_names, layer_names = load_cell(
        args.workload)
    jax = setup_jax()
    devices = check_device(jax, int(cell_def["chips"]))
    clock = CompileClock(jax)
    cell = build_cell(jax, config, traffic, args.seed, devices)
    warm = cell.run_one(0)
    if warm.error:
        raise RuntimeError(f"warm-up job failed: {warm.error}")
    check_fallbacks(cell)
    setup_s = time.perf_counter() - T_START
    print("compile in set-up: " + json.dumps(clock.summary()),
          file=sys.stderr, flush=True)

    w0 = time.perf_counter()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if args.trace:
        jobs, traced, trace, lo, hi = trace_passes(jax, cell)
        jobs = jobs + traced
    else:
        jobs, window_s = run_passes(cell, args.seconds)
    w1 = time.perf_counter()
    in_window = clock.summary(w0, w1)
    print("compile in window: " + json.dumps(in_window)
          + (" (expected none)" if in_window.get("backend_n") else ""),
          file=sys.stderr, flush=True)
    check_fallbacks(cell)
    peak = peak_memory(devices)
    device["memory_peak_bytes"] = peak
    print("memory: " + json.dumps([d.memory_stats() for d in devices]),
          file=sys.stderr, flush=True)

    if args.trace:
        run = TracedRun(config, traffic, cell.chips,
                        len(cell.pool[0][1]), traced, trace, lo, hi,
                        peaks_for(dev.device_kind))
        metrics = per_layer(layer_names, run)
        ops = trace.device_ops
        busy = [traces.length(traces.busy(o, lo, hi)) * 1e-9
                for o in ops.values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = {"device_ops": traces.top_ops(ops, lo, hi),
                     "idle_gaps": traces.longest_gaps(ops, trace.host,
                                                      lo, hi)}
    else:
        metrics = end_to_end(e2e_names, setup_s=setup_s, jobs=jobs,
                             window_s=window_s, peak_bytes=peak)
    cell.placed.clear()
    checks, failed, info = compare(cell, jobs, config["limits"])
    done = [j for j in jobs if j.error is None]
    info["label_ari_vs_truth"] = min(
        (data.adjusted_rand_index(cell.pool[j.entry][1], j.labels)
         for j in done), default=None)
    info["slowest_job_s"] = max(j.seconds for j in jobs)
    print("reference: " + json.dumps(info), file=sys.stderr, flush=True)
    result = {"correct": failed == 0, "attempted": len(jobs),
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown
    report(result, checks)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return measure(args)
    except Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr, flush=True)
        return 2


if __name__ == "__main__":
    sys.exit(main())
