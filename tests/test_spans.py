"""The host spans ``run_gpic`` records (``repro.core.spans``), read back
from a ``jax.profiler`` trace on the CPU: one run span per call, with the
front door, the launch and the health readback inside it in that order,
and one sync span for each of the four blocking reads a job makes."""
import glob
import os
import re

import jax
import pytest
from jax.profiler import ProfileData

from conftest import run_in_mesh_subprocess
from repro.core import GPICConfig, run_gpic, spans
from repro.core.health import NonFiniteInputError
from repro.data.synthetic import gaussians

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = (spans.FRONT_DOOR, spans.LAUNCH, spans.HEALTH)
#: the blocking reads of one job: the non-finite count and the
#: constant-rows check in ``validate_features``, the isolated-row count and
#: the column status in ``raise_for_health``
SYNCS = {spans.FRONT_DOOR: 2, spans.LAUNCH: 0, spans.HEALTH: 2}
CALLS = 2
N, K = 128, 3


def _recorded(trace_dir):
    """(name, start, end, call) of the program's spans in the trace, by
    start."""
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    names = {spans.RUN, spans.SYNC, *PHASES}
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.end_ns,
                        dict(e.stats).get("call"))
                       for e in line.events if e.name in names)
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _check_nesting(events, calls):
    runs = [e for e in events if e[0] == spans.RUN]
    assert len(runs) == calls
    numbers = [r[3] for r in runs]
    assert None not in numbers and numbers == sorted(set(numbers))
    for run in runs:
        inner = [e for e in events
                 if e[0] != spans.RUN and run[1] <= e[1] and e[2] <= run[2]]
        phases = [e for e in inner if e[0] in PHASES]
        assert [p[0] for p in phases] == list(PHASES)
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        syncs = [e for e in inner if e[0] == spans.SYNC]
        assert len(syncs) == sum(SYNCS.values())
        for name, start, end, _ in phases:
            assert sum(start <= s[1] and s[2] <= end
                       for s in syncs) == SYNCS[name]
    # every span of the program lies inside a call
    assert all(any(r[1] <= e[1] and e[2] <= r[2] for r in runs)
               for e in events)


def _config(**kw):
    return GPICConfig(affinity_kind="rbf", sigma=0.5, max_iter=30, **kw)


@pytest.mark.parametrize("engine", ["explicit", "streaming"])
def test_run_gpic_records_its_spans(tmp_path, engine):
    x = gaussians(N, k=K, seed=0)[0]
    cfg = _config(engine=engine)
    run_gpic(x, K, cfg)                       # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(CALLS):
            jax.block_until_ready(run_gpic(x, K, cfg).labels)
    finally:
        jax.profiler.stop_trace()
    _check_nesting(_recorded(tmp_path), CALLS)


def test_sharded_run_gpic_records_its_spans(tmp_path):
    run_in_mesh_subprocess(f"""
        import jax
        from repro.core import GPICConfig, run_gpic
        from repro.core.distributed import shard_points
        from repro.data.synthetic import gaussians
        mesh = jax.make_mesh((4,), ("data",))
        x = shard_points(gaussians({N}, k={K}, seed=0)[0], mesh, "data")
        cfg = GPICConfig(engine="explicit", mesh=mesh, affinity_kind="rbf",
                         sigma=0.5, max_iter=30)
        run_gpic(x, {K}, cfg)
        jax.profiler.start_trace({str(tmp_path)!r})
        for _ in range({CALLS}):
            jax.block_until_ready(run_gpic(x, {K}, cfg).labels)
        jax.profiler.stop_trace()
    """, devices=4, timeout=600)
    _check_nesting(_recorded(tmp_path), CALLS)


def test_a_failed_call_still_closes_its_spans(tmp_path):
    """A typed error at the front door ends the run span with it."""
    x = gaussians(N, k=K, seed=0)[0]
    x[3, 0] = float("nan")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(NonFiniteInputError):
            run_gpic(x, K, _config())
    finally:
        jax.profiler.stop_trace()
    names = [e[0] for e in _recorded(tmp_path)]
    assert names == [spans.RUN, spans.FRONT_DOOR, spans.SYNC]


def test_span_names_are_spelled_only_in_the_span_module():
    """The program and the benchmark's readers take the names from
    ``repro.core.spans``; none spells one as a string of its own."""
    names = (spans.RUN, spans.FRONT_DOOR, spans.LAUNCH, spans.HEALTH,
             spans.SYNC)
    quoted = re.compile("|".join(rf"[\"']{re.escape(n)}[\"']"
                                 for n in names))
    files = (glob.glob(os.path.join(ROOT, "src", "**", "*.py"),
                       recursive=True)
             + glob.glob(os.path.join(ROOT, "chipbench", "**", "*.py"),
                         recursive=True))
    own = os.path.join(ROOT, "src", "repro", "core", "spans.py")
    spelled = [f for f in files
               if f != own and quoted.search(open(f).read())]
    assert spelled == []
