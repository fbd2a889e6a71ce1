"""A cell's job loop and its result line, on the CPU at a small n, with
the look for a chip skipped: what the driver reads is all there, and a
run whose timed path is broken underneath comes out not correct."""
import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from stubbed_run import harness, run_cell  # noqa: E402

CELL = "exp2-smiley-45k-explicit"
N = 384


def test_loop_and_result_line():
    rc, res, err = run_cell(CELL, n=N)
    assert rc == 0, err
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    # whole passes over the pool of 4
    assert res["attempted"] >= 4 and res["attempted"] % 4 == 0
    assert set(res["metrics"]) == {"setup_s", "time_to_labels_s",
                                   "peak_hbm_gb"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1
    checks = res["checks"]
    assert set(checks) == {"emb_err", "n_iter_err", "label_err"}
    assert checks["emb_err"]["value"] <= checks["emb_err"]["limit"]
    assert checks["n_iter_err"]["value"] <= checks["n_iter_err"]["limit"]
    assert checks["label_err"]["value"] == 0
    # each number beside its limit also ends stderr
    assert err.strip().splitlines()[-1].startswith("check label_err:")
    assert "compile in window: {}" in err


def test_p95_only_where_listed():
    rc, res, err = run_cell("exp2-subsample-4500-explicit", n=4 * N)
    assert rc == 0, err
    assert "time_to_labels_p95_s" in res["metrics"]
    assert res["correct"] is True


def test_traced_run_line():
    rc, res, err = run_cell(CELL, n=N, trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU trace has no device plane: the device readers find nothing
    # and their metrics are left out, never reported as 0
    assert res["metrics"] == {"power.sweeps_per_job": {
        "value": pytest.approx(res["metrics"]["power.sweeps_per_job"]
                               ["value"]), "unit": "sweeps"}}
    assert res["metrics"]["power.sweeps_per_job"]["value"] > 1


def test_refuses_without_a_chip(capsys):
    """On the CPU the real look for a chip refuses: exit 2, no result."""
    rc = harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "no TPU" in out.err


def test_refuses_an_unknown_cell(capsys):
    assert harness.main(["--workload", "nope", "--seed", "1",
                         "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


# --- faults planted under the timed path ----------------------------------

def _state_unchanged(monkeypatch):
    """Every sweep returns the state it was given."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "degree_normalized_matmat",
                        lambda a, v, d, **kw: v)


def _half_left_out(monkeypatch):
    """Each sweep takes half of the points and doubles their sum."""
    from repro.kernels import ops
    sweep = ops.degree_normalized_matmat

    def half(a, v, d, **kw):
        keep = jnp.arange(v.shape[0])[:, None] < v.shape[0] // 2
        return 2.0 * sweep(a, jnp.where(keep, v, 0.0), d, **kw)

    monkeypatch.setattr(ops, "degree_normalized_matmat", half)


def _labels_altered(monkeypatch):
    """k-means hands back labels with a few points moved to the next
    cluster."""
    import importlib
    gpic_mod = importlib.import_module("repro.core.gpic")
    kmeans = gpic_mod.kmeans

    def altered(*args, **kw):
        labels, cents = kmeans(*args, **kw)
        k = cents.shape[0]
        return labels.at[:8].set((labels[:8] + 1) % k), cents

    monkeypatch.setattr(gpic_mod, "kmeans", altered)


def _stop_at_half(monkeypatch):
    """The power loop's stop rule fires at half the sweeps it should."""
    from chipbench import control
    from repro.core import power
    # recorded so that ``monkeypatch.undo`` puts the loop back
    monkeypatch.setattr(power, "_run_loop_state", power._run_loop_state)
    control.FAULTS["half_stop"]()


#: the number each fault has to fail; None where any may
CAUGHT_BY = {_stop_at_half: "n_iter_err"}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _labels_altered, _stop_at_half])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    jax.clear_caches()
    fault(monkeypatch)
    try:
        rc, res, err = run_cell(CELL, n=N, seconds=0.0)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert rc == 0, err
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0
    if fault in CAUGHT_BY:
        check = res["checks"][CAUGHT_BY[fault]]
        assert check["value"] > check["limit"], res["checks"]


FOUR_CHIPS_NO_EXCHANGE = """
import json, sys
import jax.numpy as jnp
sys.path.insert(0, {tests!r})
from stubbed_run import run_cell
from repro.core import operators

reductions = operators.mesh_reductions

def no_exchange(axes):
    psum, pmax, gather = reductions(axes)
    # each chip takes its own block for every other chip's
    return psum, pmax, lambda x: jnp.tile(x, (4,) + (1,) * (x.ndim - 1))

if sys.argv[-1] == "fault":
    operators.mesh_reductions = no_exchange
rc, res, err = run_cell("exp2-smiley-90k-explicit-4chip", n={n})
print(json.dumps({{"rc": rc, "res": res, "err": err[-2000:]}}))
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_four_chips_without_the_exchange_is_not_correct(fault):
    """On four virtual devices: the sharded run is correct, and leaving out
    the all-gather of V between chips makes it not correct."""
    from repro.testing import run_mesh_subprocess
    code = FOUR_CHIPS_NO_EXCHANGE.format(
        tests=os.path.dirname(os.path.abspath(__file__)), n=4 * N)
    code = ("import sys\nsys.argv.append(%r)\n" % ("fault" if fault else "")
            + code)
    out = json.loads(run_mesh_subprocess(code, devices=4).splitlines()[-1])
    assert out["rc"] == 0, out["err"]
    assert out["res"]["correct"] is (not fault), out["res"]["checks"]
