"""From a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: the operations each chip ran (one list per device, from its
"XLA Ops" line) and the host's spans (``TraceAnnotation`` and JAX's own
host events, from the host plane). The reductions below work on those
plain lists, so tests can feed them recorded or hand-made events.

Times are in nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import glob
import gzip
import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO op's name without its number: a device event is named
        by its HLO text, ``%degree_normalized_matmat.6 = f32[...] ...``,
        whose op is ``degree_normalized_matmat`` (a Pallas kernel takes
        the name of the jitted function that calls it)."""
        head = self.name.split(" = ", 1)[0].split()[0] if self.name else ""
        head = head.lstrip("%")
        stem, _, num = head.rpartition(".")
        return stem if stem and num.isdigit() else head

    @property
    def container(self) -> bool:
        """A control-flow op whose time is its body's ops."""
        return self.op in CONTAINERS


@dataclass
class Trace:
    #: device id -> the operations that device ran, by start time
    device_ops: dict[str, list[Event]]
    #: host thread name -> the spans recorded on it
    host: dict[str, list[Event]]

    def annotations(self, name: str) -> list[Event]:
        """Host spans called ``name`` (the harness's per-job spans)."""
        return sorted((e for evs in self.host.values() for e in evs
                       if e.name == name), key=lambda e: e.start)


DEVICE_OPS_LINE = "XLA Ops"
#: ops recorded around the ops they run, whose intervals hold no work of
#: their own
CONTAINERS = ("while", "conditional", "call")


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (gzipped when it ends in ``.gz``), or the
    newest under a trace directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = newest_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device_ops, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops[plane.name] = sorted(
                        (_event(e) for e in line.events),
                        key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(
                    _event(e) for e in line.events)
    return Trace(device_ops=device_ops, host=host)


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.end_ns))


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of ``events`` cut to [lo, hi]."""
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover) -> list[tuple[float, float]]:
    """The parts of ``intervals`` (a union) that ``cover`` (a union)
    leaves uncovered."""
    out, cover = [], list(cover)
    for s, e in intervals:
        cur = s
        for cs, ce in cover:
            if ce <= cur or cs >= e:
                continue
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
        if cur < e:
            out.append((cur, e))
    return out


def busy(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the intervals in which an op ran, inside [lo, hi]."""
    return union(clip([e for e in ops if not e.container], lo, hi))


def idle_gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    return subtract([(lo, hi)], busy(ops, lo, hi))


def matching(ops, names) -> list[Event]:
    """Ops called one of ``names``."""
    names = (names,) if isinstance(names, str) else tuple(names)
    return [e for e in ops if e.op in names]


def op_seconds(ops, names, lo: float, hi: float) -> float:
    """Device seconds of the ops matching ``names`` inside [lo, hi]."""
    return length(clip(matching(ops, names), lo, hi)) * 1e-9


#: HLO names of the collectives XLA emits for psum / pmax / all_gather /
#: ppermute
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


def is_collective(e: Event) -> bool:
    return any(c in e.op for c in COLLECTIVES)


def exposed_collective(ops, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which a collective ran on this device and no
    other operation did."""
    leaves = [e for e in ops if not e.container]
    coll = union(clip([e for e in leaves if is_collective(e)], lo, hi))
    compute = union(clip([e for e in leaves if not is_collective(e)],
                         lo, hi))
    return length(subtract(coll, compute)) * 1e-9


def top_ops(device_ops: dict, lo: float, hi: float, count: int = 10):
    """[op, seconds] of the ops that took most device time in [lo, hi],
    averaged over the devices; control-flow containers left out."""
    totals: dict[str, float] = {}
    for ops in device_ops.values():
        for s, e, ev in ((max(ev.start, lo), min(ev.end, hi), ev)
                         for ev in ops if not ev.container):
            if e > s:
                totals[ev.op] = totals.get(ev.op, 0.0) + (e - s) * 1e-9
    per = max(len(device_ops), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:count]
    return [[name, secs / per] for name, secs in ranked]


def host_activity(host: dict, at: float, skip=()) -> str:
    """The innermost host span running at time ``at`` ("idle host" when
    none), leaving out the names in ``skip``."""
    best = None
    for evs in host.values():
        for e in evs:
            if e.start <= at < e.end and e.name not in skip and (
                    best is None or e.dur < best.dur):
                best = e
    return best.name if best is not None else "idle host"


def longest_gaps(device_ops: dict, host: dict, lo: float, hi: float,
                 count: int = 10):
    """[what the host was doing, seconds] for the longest idle gaps of the
    chip whose gaps are longest in [lo, hi]."""
    gaps = []
    for ops in device_ops.values():
        gaps.extend(idle_gaps(ops, lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_activity(host, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:count]]
