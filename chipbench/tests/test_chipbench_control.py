"""The control comes out not correct, the program correct: the readings
behind each limit, repeated on the CPU at a size a test run holds.

The control is the reference put in the program's place, one bf16 pass
per product (``control.py``; PERF.md says why not ``high``). On the chip
the same comparison runs at each cell's own size (``control.py``)."""
import contextlib
import io
import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from stubbed_run import harness  # noqa: E402

from chipbench import control  # noqa: E402

SEEDS = "4000000001,4000000002,4000000003"


@pytest.mark.parametrize("cell, n", [("exp2-smiley-45k-explicit", 1024),
                                     ("exp2-smiley-45k-streaming", 1024)])
def test_control_fails_the_limit_and_the_program_meets_it(monkeypatch,
                                                          cell, n):
    load = harness.load_cell

    def small(name):
        c, config, traffic, e2e, layers = load(name)
        return c, dict(config, n=n), traffic, e2e, layers

    monkeypatch.setattr(harness, "load_cell", small)
    monkeypatch.setattr(harness, "setup_jax", lambda: jax)
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        control.main(["--workload", cell, "--seeds", SEEDS,
                      "--control-seeds", SEEDS, "--precisions", "default"],
                     check_device=lambda jax, chips: jax.devices()[:chips])
    rows = [json.loads(line) for line in out.getvalue().splitlines()]
    limits = small(cell)[1]["limits"]
    program = [r for r in rows if r.get("side") == "program"]
    ctrl = [r for r in rows if r.get("side") == "control"]
    assert len(program) == len(ctrl) == 12
    assert all(r["emb_err"] <= limits["emb_err"] for r in program)
    assert all(r["n_iter_err"] <= limits["n_iter_err"] for r in program)
    assert all(r["label_err"] <= limits["label_err"] for r in program)
    # every control run fails at least one of the numbers
    assert all(r["emb_err"] > limits["emb_err"]
               or r["label_err"] > limits["label_err"] for r in ctrl)
