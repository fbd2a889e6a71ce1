#!/usr/bin/env python3
"""Smoke run of GPIC on a TPU: the main path, at the paper's size, on the chip.

    python chip_smoke.py                 # one chip: reference + paper phases
    python chip_smoke.py --four-chips    # the sharded engines on 4 chips

Everything runs through the user's entry point, ``run_gpic`` with a
``GPICConfig``, in this one process (a chip belongs to one process at a
time, so nothing here starts a JAX child).

One chip:

- reference phase, n = 4,096 (``gaussians``, ``smiley``): the explicit and
  the streaming engine against ``pic_reference`` run on the chip at
  ``default_matmul_precision("highest")``. Labels must agree at ARI >= 0.99
  and embeddings within :data:`EMB_TOL` (relative to the column's largest
  reference entry).
- paper phase, Experiment II at n = 45,000 (``cassini``, ``gaussians``,
  ``shapes``, ``smiley``, ``max_iter=400``): the explicit engine with f32 A,
  the streaming engine, and one explicit run with bf16 A on ``gaussians``.
  The streaming and the bf16 labels must agree with the explicit f32 ones
  at ARI >= 0.99, and every run must reach :data:`TRUTH_FLOOR` against
  the ground truth.

Four chips (``--four-chips``, only this phase): the sharded streaming ring
and the sharded explicit engine on a 4-device mesh at n = 45,000
(``gaussians``, ``smiley``), each compared with the one-chip run of the
same config: ARI >= 0.99, ``n_iter`` within :data:`N_ITER_TOL`, and
:data:`TRUTH_FLOOR` against the ground truth.

Any failure exits non-zero: no TPU, interpret mode, a non-finite embedding,
an unusable health report, a kernel that fell back to its jnp reference,
or a comparison below its bound. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: paper Experiment II settings (benchmarks/bench_exp2.py)
SIGMAS = {"cassini": 0.3, "gaussians": 0.3, "shapes": 0.3, "smiley": 0.15}
N_VECTORS = {"cassini": 2, "gaussians": 1, "shapes": 1, "smiley": 1}
PAPER_N = 45_000
REF_N = 4_096
MAX_ITER = 400
MIN_ARI = 0.99

#: embedding tolerance of the reference phase, relative to the largest
#: |entry| of the reference column. Engine and reference sum the same
#: products in different orders (tiles vs one dense matmul): one n-term f32
#: sum carries a worst-case relative error of n·2^-24 = 2.4e-4 at n = 4,096,
#: and since the stop test (eps = 1e-5/n) sits at f32 resolution the two
#: may stop a sweep or two apart, each late sweep moving entries by a few
#: 1e-4 of the largest. 1e-3 covers both; a wrong tile, mask or degree
#: moves entries by O(1).
EMB_TOL = 1e-3

#: allowed |n_iter(4 chips) - n_iter(1 chip)|. The stop test compares the
#: per-sweep change with eps = 1e-5/n, close to f32 resolution, so the
#: order in which stripes and ring stages sum can move the crossing by a
#: sweep or two: on a v5e the 4-chip ring stopped one sweep after one chip
#: (gaussians, 16 vs 15) and the engines two after pic_reference (smiley
#: at n = 4,096, 34 vs 32). Runs stop after 15-43 sweeps, so 4 leaves room
#: for that and still refuses a sharded run that needs many more sweeps.
N_ITER_TOL = 4

#: least label ARI against the ground truth at n = 45,000. gaussians, shapes
#: and smiley are separated exactly by this configuration (ARI 1.0 on a
#: v5e); PIC's embedding splits cassini's three lobes only in part (0.653
#: on a v5e, the same on both engines), so its floor is set below that
#: reading and above a collapse into fewer clusters.
TRUTH_FLOOR = {"cassini": 0.6, "gaussians": 0.99, "shapes": 0.99,
               "smiley": 0.99}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check_device():
    """Refuse anything but compiled kernels on a TPU; returns jax."""
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        fail("REPRO_FORCE_INTERPRET is set: the kernels must run compiled "
             "on the TPU, never in interpret mode")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r} "
             f"({dev.device_kind}); a CPU run is not a chip run")
    from repro.kernels import ops
    if ops._INTERPRET:
        fail("Pallas kernels are in interpret mode on a TPU")
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"(platform {dev.platform})", flush=True)
    return jax


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def memory_report(phase, devs, clock) -> None:
    """Every allocator counter of each device, once per phase: the per-run
    ``peak_bytes_in_use`` lines are process-wide peaks, and what each
    counter covers (arrays only, or program temporaries too) depends on
    the runtime, so the whole record is printed. With it, the persistent
    compile cache's hits and misses so far, and the seconds spent so far
    in each compile stage (summed per event: a trace nested in another
    counts in both)."""
    report(phase=phase, memory_stats=[dev.memory_stats() for dev in devs],
           compile_cache=dict(clock.cache),
           compile_stage_s=dict(clock.stage_s))


class CompileClock:
    """Wall seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events. Traces of the kernels' inner jits nest inside the
    outer one, so the clock keeps each event's interval and counts their
    union: a run's compile time is the union inside the call, its run
    time the rest of the call's wall time."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "backend"}
    CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                    "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self, jax):
        self.spans: list[tuple[float, float]] = []
        self.cache = {"hits": 0, "misses": 0}
        self.stage_s = dict.fromkeys(self.EVENTS.values(), 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))
            self.stage_s[self.EVENTS[event]] += duration

    def _on_event(self, event, **_):
        if event in self.CACHE_EVENTS:
            self.cache[self.CACHE_EVENTS[event]] += 1

    def seconds_since(self, t0: float) -> float:
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total


def timed_run(jax, clock, x, k, cfg, key):
    """``run_gpic`` timed to ``block_until_ready``: (result, compile_s,
    run_s)."""
    from repro.core import run_gpic
    t0 = time.perf_counter()
    res = run_gpic(x, k, cfg, key=key)
    jax.block_until_ready(res)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds_since(t0)
    return res, compile_s, wall - compile_s


def check_result(res, tag):
    """Finite embedding and no kernel fallback (``run_gpic`` has already
    raised for an unusable health report)."""
    import numpy as np
    from repro.kernels import ops
    if not np.isfinite(np.asarray(res.embeddings)).all():
        fail(f"{tag}: non-finite embedding")
    notes = [n for n in res.health.notes if n.startswith("kernel_fallback")]
    if notes:
        fail(f"{tag}: kernel fell back to its reference: {notes}")
    if ops.kernel_fallbacks():
        fail(f"{tag}: kernel fallbacks recorded: {ops.kernel_fallbacks()}")


def check_truth(tag, name, agree) -> None:
    if agree < TRUTH_FLOOR[name]:
        fail(f"{tag}: label ARI vs ground truth {agree} < "
             f"{TRUTH_FLOOR[name]}")


def ari(a, b) -> float:
    import numpy as np
    from repro.core import adjusted_rand_index
    return float(adjusted_rand_index(np.asarray(a), np.asarray(b)))


def report(**fields) -> None:
    print(json.dumps(fields), flush=True)


def reference_phase(jax, clock):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import GPICConfig, pic_reference
    from repro.data import dataset_by_name
    dev = jax.devices()[0]
    for name in ("gaussians", "smiley"):
        x, y, k = dataset_by_name(name, REF_N, seed=0)
        x = jnp.asarray(x)
        key = jax.random.key(1)
        with jax.default_matmul_precision("highest"):
            ref = pic_reference(x, k, key=key, affinity_kind="rbf",
                                sigma=SIGMAS[name], max_iter=MAX_ITER,
                                n_vectors=N_VECTORS[name])
            jax.block_until_ready(ref)
        ref_emb = np.asarray(ref.embeddings)
        scale = np.abs(ref_emb).max(axis=0)
        for engine in ("explicit", "streaming"):
            cfg = GPICConfig(engine=engine, affinity_kind="rbf",
                             sigma=SIGMAS[name], n_vectors=N_VECTORS[name],
                             max_iter=MAX_ITER)
            tag = f"reference/{name}/{engine}"
            res, comp_s, run_s = timed_run(jax, clock, x, k, cfg, key)
            check_result(res, tag)
            err = float((np.abs(np.asarray(res.embeddings) - ref_emb)
                         / scale).max())
            agree = ari(res.labels, ref.labels)
            report(phase="reference", dataset=name, engine=engine, n=REF_N,
                   compile_s=comp_s, run_s=run_s, n_iter=int(res.n_iter),
                   n_iter_ref=int(ref.n_iter), ari_vs_ref=agree,
                   ari_truth=ari(y, res.labels), emb_rel_err=err,
                   emb_tol=EMB_TOL, peak_bytes_in_use=peak_bytes(dev))
            if agree < MIN_ARI:
                fail(f"{tag}: label ARI vs pic_reference {agree} < {MIN_ARI}")
            if not err <= EMB_TOL:
                fail(f"{tag}: embedding differs from pic_reference by "
                     f"{err} (relative) > {EMB_TOL}")
    memory_report("reference", [dev], clock)


def paper_phase(jax, clock):
    import jax.numpy as jnp
    from repro.core import GPICConfig
    from repro.data import dataset_by_name
    dev = jax.devices()[0]
    for name in ("cassini", "gaussians", "shapes", "smiley"):
        x, y, k = dataset_by_name(name, PAPER_N, seed=0)
        x = jnp.asarray(x)
        key = jax.random.key(1)
        cfg = GPICConfig(affinity_kind="rbf", sigma=SIGMAS[name],
                         n_vectors=N_VECTORS[name], max_iter=MAX_ITER)
        runs = [("explicit", "float32", cfg),
                ("streaming", "float32", cfg.with_(engine="streaming"))]
        if name == "gaussians":
            runs.append(("explicit", "bfloat16",
                         cfg.with_(a_dtype=jnp.bfloat16)))
        labels = {}
        for engine, a_dtype, c in runs:
            tag = f"paper/{name}/{engine}/{a_dtype}"
            res, comp_s, run_s = timed_run(jax, clock, x, k, c, key)
            check_result(res, tag)
            labels[(engine, a_dtype)] = res.labels
            fields = dict(phase="paper", dataset=name, engine=engine,
                          a_dtype=a_dtype, n=PAPER_N, compile_s=comp_s,
                          run_s=run_s, n_iter=int(res.n_iter),
                          ari_truth=ari(y, res.labels),
                          peak_bytes_in_use=peak_bytes(dev))
            if (engine, a_dtype) != ("explicit", "float32"):
                fields["ari_vs_explicit_f32"] = ari(
                    labels[("explicit", "float32")], res.labels)
            report(**fields)
            check_truth(tag, name, fields["ari_truth"])
            agree = fields.get("ari_vs_explicit_f32", 1.0)
            if agree < MIN_ARI:
                fail(f"{tag}: label ARI vs explicit f32 {agree} < {MIN_ARI}")
    memory_report("paper", [dev], clock)


def four_chip_phase(jax, clock):
    import jax.numpy as jnp
    from repro.core import GPICConfig
    from repro.core.distributed import shard_points
    from repro.data import dataset_by_name
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    devs = devs[:4]
    mesh = jax.make_mesh((4,), ("data",), devices=devs)
    cases = []
    # the sharded runs come first: memory peaks are process-wide, so the
    # per-device peaks printed here are the sharded engines' own
    for name in ("gaussians", "smiley"):
        x, y, k = dataset_by_name(name, PAPER_N, seed=0)
        xs = shard_points(x, mesh, "data")
        cfg = GPICConfig(affinity_kind="rbf", sigma=SIGMAS[name],
                         n_vectors=N_VECTORS[name], max_iter=MAX_ITER)
        for engine in ("streaming", "explicit"):
            c = cfg.with_(engine=engine)
            tag = f"four_chips/{name}/{engine}"
            res, comp_s, run_s = timed_run(
                jax, clock, xs, k, c.with_(mesh=mesh), jax.random.key(1))
            check_result(res, tag)
            truth = ari(y, res.labels)
            report(phase="four_chips", dataset=name, engine=engine,
                   n=PAPER_N, rows_per_chip=PAPER_N // 4, compile_s=comp_s,
                   run_s=run_s, n_iter=int(res.n_iter), ari_truth=truth,
                   peak_bytes_in_use=[peak_bytes(d) for d in devs])
            check_truth(tag, name, truth)
            cases.append((tag, name, x, k, c, res))
    memory_report("four_chips", devs, clock)
    for tag, name, x, k, c, res in cases:
        one, comp_s, run_s = timed_run(jax, clock, jnp.asarray(x), k, c,
                                       jax.random.key(1))
        check_result(one, tag + "/one_chip")
        agree = ari(one.labels, res.labels)
        d_iter = abs(int(one.n_iter) - int(res.n_iter))
        report(phase="four_chips_vs_one", dataset=name, engine=c.engine,
               n=PAPER_N, compile_s=comp_s, run_s=run_s,
               n_iter_one_chip=int(one.n_iter), n_iter_four=int(res.n_iter),
               ari_vs_one_chip=agree, n_iter_tol=N_ITER_TOL)
        if agree < MIN_ARI:
            fail(f"{tag}: 4-chip vs 1-chip label ARI {agree} < {MIN_ARI}")
        if d_iter > N_ITER_TOL:
            fail(f"{tag}: n_iter differs by {d_iter} > {N_ITER_TOL}")
    return len(devs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-chip phase and its "
                         "one-chip comparison")
    args = ap.parse_args()

    jax = check_device()
    from repro.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    clock = CompileClock(jax)
    if args.four_chips:
        count = four_chip_phase(jax, clock)
    else:
        reference_phase(jax, clock)
        paper_phase(jax, clock)
        count = len(jax.devices())
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
