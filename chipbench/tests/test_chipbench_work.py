"""The work functions and the roofline share against counts made by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.peaks import PEAKS, peaks_for, roofline_share  # noqa: E402
from chipbench.work import build_work, sweep_work  # noqa: E402

V5E = PEAKS["TPU v5 lite"]
ONE = {"chips": 1, "a_dtype": "float32", "m": 2}
FOUR = {"chips": 4, "a_dtype": "float32", "m": 2}


def test_explicit_sweep_reads_a_once():
    flops, nbytes = sweep_work(ONE, 45_000, 1)
    assert flops == 2 * 45_000 ** 2                  # one multiply-add per entry
    # A (8.1 GB) once, V read, U written, degrees read: 4 B each
    assert nbytes == 45_000 ** 2 * 4 + (45_000 + 45_000 + 45_000) * 4


def test_four_chips_each_sweep_their_stripe():
    flops, nbytes = sweep_work(FOUR, 90_000, 1)
    rows = 22_500
    assert flops == 2 * rows * 90_000
    assert nbytes == rows * 90_000 * 4 + (90_000 + rows + rows) * 4


def test_bf16_storage_halves_the_a_bytes():
    _, f32 = sweep_work(ONE, 4_500, 1)
    _, bf16 = sweep_work(dict(ONE, a_dtype="bfloat16"), 4_500, 1)
    assert f32 - bf16 == 4_500 ** 2 * 2


def test_build_writes_a_once():
    flops, nbytes = build_work(ONE, 45_000)
    assert flops == 2 * 45_000 ** 2 * 2              # the m = 2 distance dot
    assert nbytes == 45_000 ** 2 * 4 + 2 * 45_000 * 2 * 4 + 45_000 * 4


@pytest.mark.parametrize("ms, share", [(19.0, 52.0), (9.89, 100.0)])
def test_sweep_roofline_share_is_hbm_bound(ms, share):
    """At n = 45,000 reading A takes 8.1e9 B / 819e9 B/s = 9.89 ms, and the
    2n^2 flops 0.02 ms: the bytes bound the sweep."""
    flops, nbytes = sweep_work(ONE, 45_000, 1)
    assert nbytes / V5E["hbm_bytes_per_s"] > flops / V5E["flops_per_s"]
    assert roofline_share(flops, nbytes, ms * 1e-3, V5E) == pytest.approx(
        share, rel=2e-3)


def test_flop_bound_share():
    # 197e12 flops take 1 s at the peak; 1 byte is nothing against that
    assert roofline_share(197e12, 1.0, 2.0, V5E) == pytest.approx(50.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")
