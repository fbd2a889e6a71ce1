"""The reduction from trace events to device numbers, by hand-made events
and on small traces recorded on a TPU v5e (``data/``)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import trace  # noqa: E402
from chipbench.trace import Event  # noqa: E402


def ev(name, start, end):
    return Event(name, float(start), float(end))


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    ops = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40), ev("d", 50, 70)]
    assert trace.busy(ops, 0, 60) == [(0, 20), (30, 40), (50, 60)]
    assert trace.length(trace.busy(ops, 0, 60)) == 40
    assert trace.idle_gaps(ops, 0, 60) == [(20, 30), (40, 50)]


def test_ops_are_named_by_their_hlo_op():
    assert ev("%degree_normalized_matmat.6 = f32[45056,1]{1,0} "
              "custom-call(f32[45056,45056] %p)", 0, 1).op == \
        "degree_normalized_matmat"
    assert ev("%all-gather.3 = f32[90000,1] all-gather(...)", 0, 1).op == \
        "all-gather"
    assert ev("%while.56 = (s32[]) while(...)", 0, 1).container
    assert not ev("%fusion.2 = f32[4] fusion(...)", 0, 1).container


def test_kernel_time_sums_the_ops_of_one_name():
    ops = [ev("%fusion.1 = f32[8] fusion()", 0, 4),
           ev("%degree_normalized_matmat.6 = f32[8] custom-call()", 4, 10),
           ev("%degree_normalized_matmat.7 = f32[8] custom-call()", 10, 13)]
    # 9 ns of the kernel inside the window, in seconds
    assert trace.op_seconds(ops, "degree_normalized_matmat", 0, 100) == \
        pytest.approx(9e-9)
    assert trace.op_seconds(ops, "degree_normalized_matmat", 5, 11) == \
        pytest.approx(6e-9)


def test_a_loop_counts_by_its_body():
    """The while op spans its body; idle time between body ops stays
    idle."""
    ops = [ev("%while.1 = () while()", 0, 100), ev("%k.1 = f()", 10, 40),
           ev("%k.2 = f()", 60, 90)]
    assert trace.length(trace.busy(ops, 0, 100)) == 60


def test_exposed_collective_is_the_part_no_compute_covers():
    ops = [ev("%while.3 = () while()", 0, 40),
           ev("%all-gather.1 = f32[8] all-gather()", 0, 10),
           ev("%fusion = f32[8] fusion()", 2, 6),
           ev("%all-reduce.2 = f32[] all-reduce()", 20, 24),
           ev("%fusion.2 = f32[8] fusion()", 22, 30)]
    # all-gather 0-10 less compute 2-6: 6 ns; all-reduce 20-24 less 22-30: 2
    assert trace.exposed_collective(ops, 0, 100) == pytest.approx(8e-9)


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    device = {"/device:TPU:0": [ev("k", 0, 10), ev("k", 30, 40)]}
    host = {"python": [ev("chipbench.job", 0, 40),
                       ev("run_gpic", 5, 35), ev("device_get", 12, 28)]}
    assert trace.longest_gaps(device, host, 0, 40) == [
        ["device_get", pytest.approx(20e-9)]]


def test_top_ops_average_over_devices():
    device = {"a": [ev("x", 0, 10), ev("y", 10, 12)],
              "b": [ev("x", 0, 6)]}
    assert trace.top_ops(device, 0, 100) == [
        ["x", pytest.approx(8e-9)], ["y", pytest.approx(1e-9)]]


# --- a trace recorded on a TPU v5e: one pass of exp2-subsample-4500-explicit
# (4 jobs at n = 4,500; 41 + 43 + 46 + 49 sweeps)

RECORDED = os.path.join(HERE, "data", "v5e_subsample_pass.xplane.pb.gz")
SWEEPS = 41 + 43 + 46 + 49


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(RECORDED)
    spans = tr.annotations("chipbench.job")
    return tr, spans[0].start, spans[-1].end, spans


def test_recorded_trace_has_one_chip_and_four_jobs(recorded):
    tr, lo, hi, spans = recorded
    assert list(tr.device_ops) == ["/device:TPU:0"]
    assert len(spans) == 4
    assert 0.05 < (hi - lo) * 1e-9 < 0.1


def test_recorded_kernels_count_by_the_algorithm(recorded):
    """One sweep kernel per sweep, one build per job, and 25 Lloyd
    assignments plus the final one per job."""
    tr, lo, hi, _ = recorded
    ops = tr.device_ops["/device:TPU:0"]
    assert len(trace.matching(ops, "degree_normalized_matmat")) == SWEEPS
    assert len(trace.matching(ops, "affinity_and_degree")) == 4
    assert len(trace.matching(ops, "kmeans_assign")) == 4 * 26


def test_recorded_kernel_time_busy_union_and_idle_share(recorded):
    tr, lo, hi, _ = recorded
    ops = tr.device_ops["/device:TPU:0"]
    sweep = trace.op_seconds(ops, "degree_normalized_matmat", lo, hi)
    busy = trace.length(trace.busy(ops, lo, hi)) * 1e-9
    assert sweep / SWEEPS == pytest.approx(0.197e-3, rel=0.01)
    assert sweep < busy < (hi - lo) * 1e-9
    assert 1 - busy / ((hi - lo) * 1e-9) == pytest.approx(0.383, abs=0.005)
    # the union counts no overlap twice
    leaves = trace.clip([e for e in ops if not e.container], lo, hi)
    assert busy <= sum(e - s for s, e in leaves) * 1e-9


def test_recorded_breakdown(recorded):
    tr, lo, hi, _ = recorded
    top = trace.top_ops(tr.device_ops, lo, hi)
    assert top[0][0] == "degree_normalized_matmat"
    assert len(top) == 10 and all(s > 0 for _, s in top)
    gaps = trace.longest_gaps(tr.device_ops, tr.host, lo, hi)
    assert len(gaps) == 10 and gaps[0][1] >= gaps[-1][1] > 0
    # no single chip-side gap is longer than a job's host round trip
    assert gaps[0][1] < 0.005


# --- one pass of exp2-smiley-90k-explicit-4chip, recorded on four v5e chips
# (4 jobs at n = 90,000; 39 + 40 + 41 + 41 sweeps)

RECORDED_4 = os.path.join(HERE, "data", "v5e_4chip_pass.xplane.pb.gz")
SWEEPS_4 = 39 + 40 + 41 + 41


@pytest.fixture(scope="module")
def recorded_4():
    tr = trace.load(RECORDED_4)
    spans = tr.annotations("chipbench.job")
    return tr, spans[0].start, spans[-1].end


def test_recorded_four_chips_each_sweep_their_stripe(recorded_4):
    tr, lo, hi = recorded_4
    assert len(tr.device_ops) == 4
    for ops in tr.device_ops.values():
        assert len(trace.matching(ops, "degree_normalized_matmat")) == \
            SWEEPS_4
        # a reduction across chips in every sweep
        assert sum(trace.is_collective(e) for e in ops) > SWEEPS_4


def test_recorded_exposed_collective(recorded_4):
    """Collectives take a few microseconds a sweep that no compute covers,
    far less than the sweep (17.3 ms a chip)."""
    tr, lo, hi = recorded_4
    for ops in tr.device_ops.values():
        exposed = trace.exposed_collective(ops, lo, hi)
        assert 0 < exposed / SWEEPS_4 < 1e-4
        sweep = trace.op_seconds(ops, "degree_normalized_matmat", lo, hi)
        assert sweep / SWEEPS_4 == pytest.approx(17.3e-3, rel=0.01)
