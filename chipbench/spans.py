"""The program's own host spans in a traced run, and the chip's clock put
on the host's.

``run_gpic`` records its spans under the names ``repro.core.spans`` holds
(``RUN`` around each call; ``FRONT_DOOR``, ``LAUNCH`` and ``HEALTH``
inside it; ``SYNC`` around each blocking read). The readers take the
names from there; a program without that module has no spans, and every
reader then finds nothing.

The chip stamps its events on its own clock, skewed from the host's by up
to a fraction of a millisecond. ``clock_offset`` measures the skew from
launches the two sides both record: each host ``PjitFunction(<f>)`` and
the ``jit_<f>`` module it ran, from the chip's "XLA Modules" line, which
``chipbench/trace.py`` does not keep. Times are in nanoseconds.
"""
from __future__ import annotations

import gzip
import os
import sys

from chipbench import trace

#: where ``chipbench/run.py`` writes the traced passes
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".trace")
MODULES_LINE = "XLA Modules"
LAUNCH_PREFIX = "PjitFunction("
#: pairs of launch and module below which the offset is not read
MIN_PAIRS = 4


def names():
    """``repro.core.spans``, or None where the program records no spans."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans


def runs(run):
    """The spans of the calls of ``run_gpic`` inside the traced window.

    Only a trace of the chip counts: in one without a device plane (the
    harness rehearsed on the CPU) the spans time another backend's front
    door and launch, and every reader finds nothing, as the device
    readers do."""
    sp = names()
    if sp is None or not run.trace.device_ops:
        return []
    return [e for e in run.trace.annotations(sp.RUN)
            if run.lo <= e.start and e.end <= run.hi]


def inside(events, outer):
    """The events that lie inside one of the ``outer`` spans."""
    return [e for e in events
            if any(o.start <= e.start and e.end <= o.end for o in outer)]


def ms_per_job(run, name: str):
    """Host milliseconds of the spans called ``name`` per traced call of
    ``run_gpic``; None where the trace has no call or no such span."""
    jobs = runs(run)
    found = inside(run.trace.annotations(name), jobs)
    if not jobs or not found:
        return None
    return sum(e.dur for e in found) * 1e-6 / len(jobs)


def syncs_per_job(run):
    """Blocking reads (sync spans) per traced call of ``run_gpic``."""
    jobs = runs(run)
    if not jobs:
        return None
    return len(inside(run.trace.annotations(names().SYNC), jobs)) / len(jobs)


# ---------------------------------------------------------------------------
# one clock
# ---------------------------------------------------------------------------

def device_modules(path: str) -> list[trace.Event]:
    """The modules the first chip ran ("XLA Modules" of the lowest-numbered
    device plane), named without their fingerprint: ``jit_gpic``. ``path``
    is an ``.xplane.pb`` (gzipped when it ends in ``.gz``) or a trace
    directory, whose newest file is read."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = trace.newest_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    for plane in sorted((p for p in data.planes
                         if p.name.startswith("/device:")),
                        key=lambda p: p.name):
        for line in plane.lines:
            if line.name == MODULES_LINE:
                return sorted((trace.Event(e.name.split("(", 1)[0],
                                           float(e.start_ns),
                                           float(e.end_ns))
                               for e in line.events), key=lambda e: e.start)
    return []


def launches(host: dict) -> dict[str, list[trace.Event]]:
    """Host launches by the module they run (``jit_<f>``), in order. The
    runtime records each ``PjitFunction(<f>)`` twice, one inside the
    other: only the outer one counts."""
    out: dict[str, list[trace.Event]] = {}
    for evs in host.values():
        outer: dict[str, trace.Event] = {}
        for e in sorted((e for e in evs if e.name.startswith(LAUNCH_PREFIX)),
                        key=lambda e: (e.start, -e.end)):
            last = outer.get(e.name)
            if last is not None and e.end <= last.end:
                continue
            outer[e.name] = e
            f = "jit_" + e.name[len(LAUNCH_PREFIX):].rstrip(")")
            out.setdefault(f, []).append(e)
    return {f: sorted(evs, key=lambda e: e.start) for f, evs in out.items()}


def pairs(host: dict, modules) -> list[tuple[trace.Event, trace.Event]]:
    """(launch, module) pairs: the k-th launch of ``f`` with the k-th
    ``jit_<f>`` module that follows it in the chip's order. On a skewed
    clock a module can seem to start before its launch, so they pair by
    order, and a function whose launches and modules differ in number is
    left out."""
    by_name: dict[str, list[trace.Event]] = {}
    for m in modules:
        by_name.setdefault(m.name, []).append(m)
    out = []
    for f, ls in launches(host).items():
        ms = by_name.get(f, [])
        if len(ms) == len(ls):
            out.extend(zip(ls, ms))
    return out


def clock_offset(host: dict, modules, lo: float, hi: float):
    """Nanoseconds to take from the chip's times to put them on the host's
    clock: the least (module start - launch start) over the pairs launched
    in [lo, hi]. Negative when the chip's clock lags. None with fewer than
    ``MIN_PAIRS`` pairs."""
    gaps = [m.start - launch.start for launch, m in pairs(host, modules)
            if lo <= launch.start <= hi]
    if len(gaps) < MIN_PAIRS:
        return None
    return min(gaps)


def shifted(ops, offset: float) -> list[trace.Event]:
    return [trace.Event(e.name, e.start - offset, e.end - offset)
            for e in ops]


def idle_inside(ops, spans, lo: float, hi: float) -> float:
    """Nanoseconds in [lo, hi] in which no op ran on this chip and one of
    ``spans`` was open on the host."""
    held = trace.union(trace.clip(spans, lo, hi))
    outside = trace.subtract([(lo, hi)], held)
    return trace.length(trace.subtract(trace.idle_gaps(ops, lo, hi),
                                       outside))


def idle_in_program_ms(run, trace_dir: str | None = None):
    """Device idle milliseconds per traced call of ``run_gpic`` that fall
    inside the call's span, on the host's clock; the idlest chip."""
    jobs = runs(run)
    if not jobs:
        return None
    try:
        modules = device_modules(trace_dir or TRACE_DIR)
    except FileNotFoundError:
        return None
    offset = clock_offset(run.trace.host, modules, run.lo, run.hi)
    if offset is None:
        return None
    print(f"chipbench: clock offset {offset * 1e-6:.6f} ms",
          file=sys.stderr, flush=True)
    idle = [idle_inside(shifted(ops, offset), jobs, run.lo, run.hi)
            for ops in run.trace.device_ops.values()]
    return max(idle) * 1e-6 / len(jobs)
