"""The readers of the program's own spans (``chipbench/spans.py`` and the
metrics built on it): on hand-made events, on a stubbed traced run on the
CPU, and on traces recorded on a TPU v5e (``data/``)."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from stubbed_run import harness, run_cell  # noqa: E402

from chipbench import spans, trace  # noqa: E402
from chipbench.trace import Event  # noqa: E402
from repro.core import spans as names  # noqa: E402

MS = 1e6    # nanoseconds


def ev(name, start, end):
    return Event(name, float(start), float(end))


def read(metric, run):
    return harness.load_reader(metric)(run)


#: a chip that ran one op: the readers read a trace of the chip only
ONE_CHIP = {"/device:TPU:0": [Event("k", 0.0, 1.0)]}


def traced(host, device_ops=ONE_CHIP, lo=0, hi=100):
    return SimpleNamespace(trace=trace.Trace(device_ops, host),
                           lo=float(lo), hi=float(hi), jobs=[])


# --- hand-made events

def two_jobs():
    """Two calls of 40 ns: front door 10, launch 5, health 20, and two
    blocking reads in the front door and two in the health readback."""
    host = []
    for t in (0, 50):
        host += [ev(names.RUN, t, t + 40),
                 ev(names.FRONT_DOOR, t, t + 10),
                 ev(names.SYNC, t + 2, t + 3), ev(names.SYNC, t + 6, t + 8),
                 ev(names.LAUNCH, t + 10, t + 15),
                 ev(names.HEALTH, t + 15, t + 35),
                 ev(names.SYNC, t + 16, t + 30),
                 ev(names.SYNC, t + 31, t + 33)]
    return {"python": host}


def test_host_readers_average_over_the_calls():
    run = traced(two_jobs())
    assert read("front_door.host_ms", run) == pytest.approx(10e-6)
    assert read("launch.host_ms", run) == pytest.approx(5e-6)
    assert read("host.syncs_per_job", run) == 4


def test_host_readers_count_only_calls_inside_the_window():
    run = traced(two_jobs(), lo=45, hi=100)
    assert spans.runs(run) == [ev(names.RUN, 50, 90)]
    assert read("host.syncs_per_job", run) == 4
    assert read("front_door.host_ms", run) == pytest.approx(10e-6)


def test_host_readers_find_nothing_without_a_chip():
    """A trace with no device plane (the harness on the CPU) times another
    backend: its spans are not read."""
    run = traced(two_jobs(), device_ops={})
    assert spans.runs(run) == []
    for metric in ("front_door.host_ms", "launch.host_ms",
                   "host.syncs_per_job", "device.idle_in_program_ms"):
        assert read(metric, run) is None


def test_a_sync_outside_a_call_is_not_counted():
    host = two_jobs()
    host["python"].append(ev(names.SYNC, 42, 44))
    assert read("host.syncs_per_job", traced(host)) == 4


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    """A program from before the spans (no ``repro.core.spans``): every
    new metric is left out, none raises."""
    monkeypatch.setattr(spans, "names", lambda: None)
    run = traced(two_jobs(), {"/device:TPU:0": [ev("k", 0, 10)]})
    for metric in ("front_door.host_ms", "launch.host_ms",
                   "host.syncs_per_job", "device.idle_in_program_ms"):
        assert read(metric, run) is None


def test_launches_keep_the_outer_of_each_doubled_event():
    host = {"python": [ev("PjitFunction(gpic)", 10, 20),
                       ev("PjitFunction(gpic)", 11, 19),
                       ev("PjitFunction(equal)", 30, 31),
                       ev("PjitFunction(equal)", 30, 31),
                       ev("PjitFunction(gpic)", 40, 50),
                       ev("PjitFunction(gpic)", 41, 49)],
            "other": [ev("PjitFunction(gpic)", 60, 70)]}
    got = spans.launches(host)
    assert [e.start for e in got["jit_gpic"]] == [10, 40, 60]
    assert [e.start for e in got["jit_equal"]] == [30]


def test_pairs_go_by_order_and_skip_a_function_whose_counts_differ():
    host = {"python": [ev("PjitFunction(f)", 100, 110),
                       ev("PjitFunction(f)", 200, 210),
                       ev("PjitFunction(g)", 300, 310)]}
    # on the chip's clock the modules seem to start before their launches
    modules = [ev("jit_f", 90, 95), ev("jit_f", 190, 195),
               ev("jit_g", 280, 290), ev("jit_g", 380, 390)]
    got = spans.pairs(host, modules)
    assert [(a.start, m.start) for a, m in got] == [(100, 90), (200, 190)]


def test_clock_offset_is_the_least_gap_and_needs_four_pairs():
    host = {"python": [ev("PjitFunction(f)", t, t + 5)
                       for t in (100, 200, 300, 400)]}
    modules = [ev("jit_f", t, t + 1) for t in (96, 193, 299, 402)]
    assert spans.clock_offset(host, modules, 0, 1000) == -7
    # three pairs in the window are too few
    assert spans.clock_offset(host, modules, 150, 1000) is None


def test_idle_inside_counts_idle_time_while_a_call_is_open():
    ops = [ev("k", 10, 20), ev("k", 60, 70)]
    calls = [ev(names.RUN, 0, 40), ev(names.RUN, 50, 90)]
    # idle 0-10, 20-40 in the first call, 50-60, 70-90 in the second;
    # 40-50 is idle between calls and does not count
    assert spans.idle_inside(ops, calls, 0, 100) == 60


def test_idle_in_program_moves_the_chip_onto_the_host_clock(monkeypatch,
                                                             capsys):
    """The chip's clock lags 5 ns: unshifted, the kernel seems to start
    before its launch and a gap inside the call is missed."""
    host = {"python": [ev(names.RUN, 0, 100),
                       ev(names.RUN, 200, 300)]
            + [ev("PjitFunction(f)", t, t + 2)
               for t in (10, 30, 210, 230)]}
    modules = [ev("jit_f", t - 5, t + 15) for t in (10, 30, 210, 230)]
    device = {"/device:TPU:0": [ev("k", m.start, m.end) for m in modules]}
    monkeypatch.setattr(spans, "device_modules", lambda path: modules)
    run = traced(host, device, lo=0, hi=300)
    # on the host clock the chip is busy 10-30, 30-50 and 210-250 of the
    # calls' 200 ns: 120 ns idle, 60 a call
    assert read("device.idle_in_program_ms", run) == pytest.approx(60e-6)
    assert "chipbench: clock offset -0.000005 ms" in capsys.readouterr().err


def test_idle_in_program_takes_the_idlest_chip(monkeypatch):
    host = {"python": [ev(names.RUN, 0, 100)]
            + [ev("PjitFunction(f)", t, t + 1) for t in (0, 20, 40, 60)]}
    modules = [ev("jit_f", t, t + 10) for t in (0, 20, 40, 60)]
    device = {"/device:TPU:0": [ev("k", 0, 90)],
              "/device:TPU:1": [ev("k", 0, 50)]}
    monkeypatch.setattr(spans, "device_modules", lambda path: modules)
    run = traced(host, device, lo=0, hi=100)
    assert read("device.idle_in_program_ms", run) == pytest.approx(50e-6)


def test_idle_in_program_left_out_without_enough_pairs(monkeypatch):
    host = {"python": [ev(names.RUN, 0, 100),
                       ev("PjitFunction(f)", 10, 12)]}
    monkeypatch.setattr(spans, "device_modules",
                        lambda path: [ev("jit_f", 5, 20)])
    run = traced(host, {"/device:TPU:0": [ev("k", 5, 20)]})
    assert read("device.idle_in_program_ms", run) is None


# --- the harness on the CPU

def test_traced_run_carries_the_spans_but_reads_them_only_on_a_chip(
        tmp_path, monkeypatch):
    """The program's spans reach the harness's CPU trace, one call span a
    job with its four syncs; the trace has no device plane, so the new
    metrics are left out as the device metrics are."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    rc, res, err = run_cell("exp2-smiley-45k-explicit", n=384, trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    tr = trace.load(str(tmp_path))
    assert tr.device_ops == {}
    jobs = tr.annotations("chipbench.job")
    calls = spans.inside(tr.annotations(names.RUN), jobs)
    assert len(calls) == len(jobs) > 0
    assert len(spans.inside(tr.annotations(names.SYNC), calls)) == \
        4 * len(calls)
    for name in (names.FRONT_DOOR, names.LAUNCH, names.HEALTH):
        assert len(spans.inside(tr.annotations(name), calls)) == len(calls)
    for metric in ("front_door.host_ms", "launch.host_ms",
                   "host.syncs_per_job", "device.idle_in_program_ms"):
        assert metric not in res["metrics"]
        assert f"per-layer metric {metric} found nothing" in err


# --- traces recorded on a TPU v5e, from before the program had spans

def recorded(name):
    path = os.path.join(HERE, "data", name)
    tr = trace.load(path)
    jobs = tr.annotations("chipbench.job")
    return tr, spans.device_modules(path), jobs[0].start, jobs[-1].end


@pytest.mark.parametrize("name,lo_ms,hi_ms", [
    ("v5e_subsample_pass.xplane.pb.gz", -0.6, -0.5),
    ("v5e_4chip_pass.xplane.pb.gz", -0.2, 0.2),
])
def test_recorded_clock_offset(name, lo_ms, hi_ms):
    """Each of the 8 programs a job launches (7 eager front-door checks and
    the jitted pipeline) pairs on all 4 jobs; after the shift no module
    starts before its launch."""
    tr, modules, lo, hi = recorded(name)
    got = spans.pairs(tr.host, modules)
    assert len(got) == 32
    offset = spans.clock_offset(tr.host, modules, lo, hi)
    assert lo_ms * MS < offset < hi_ms * MS
    assert all(m.start - offset >= launch.start for launch, m in got)
    # unshifted, the subsample trace's modules start before their launches
    if hi_ms < 0:
        assert sum(m.start < launch.start for launch, m in got) > 16


# --- one pass of exp2-subsample-4500-explicit with the program's spans,
# recorded on a TPU v5e (4 jobs at n = 4,500; 46 + 43 + 41 + 48 sweeps)

SPANS = "v5e_subsample_spans.xplane.pb.gz"


@pytest.fixture(scope="module")
def with_spans():
    tr, modules, lo, hi = recorded(SPANS)
    path = os.path.join(HERE, "data", SPANS)
    run = SimpleNamespace(trace=tr, lo=lo, hi=hi,
                          jobs=tr.annotations("chipbench.job"))
    return run, modules, path


def test_recorded_spans_count_four_syncs_a_job(with_spans):
    run, _, _ = with_spans
    assert len(spans.runs(run)) == len(run.jobs) == 4
    assert read("host.syncs_per_job", run) == 4


def test_recorded_front_door_and_launch(with_spans):
    """The front door (7 eager checks and 2 reads) and the enqueue of the
    jitted pipeline take milliseconds of a 15.5 ms job."""
    run, _, _ = with_spans
    front = read("front_door.host_ms", run)
    launch = read("launch.host_ms", run)
    assert 2.0 < front < 4.0
    assert 0.5 < launch < 1.5
    call = spans.ms_per_job(run, names.RUN)
    assert front + launch < call < 20.0


def test_recorded_idle_in_program(with_spans, capsys):
    """Most of the chip's idle time falls inside the call, on the host's
    clock; no paired module starts before its launch there."""
    run, modules, path = with_spans
    offset = spans.clock_offset(run.trace.host, modules, run.lo, run.hi)
    assert -0.6 * MS < offset < -0.3 * MS
    assert all(m.start - offset >= launch.start
               for launch, m in spans.pairs(run.trace.host, modules))
    idle = spans.idle_in_program_ms(run, trace_dir=path)
    assert "chipbench: clock offset" in capsys.readouterr().err
    ops = run.trace.device_ops["/device:TPU:0"]
    busy = trace.length(trace.busy(ops, run.lo, run.hi))
    idle_a_job = (run.hi - run.lo - busy) * 1e-6 / len(run.jobs)
    assert 0.6 * idle_a_job < idle < idle_a_job
