"""Share of the roofline the explicit sweep kernel reaches: the least time
the chip needs for one sweep's work at the configuration's stated dtype
(A read once, ``work.sweep_work``) over the kernel's time per sweep."""
import importlib.util
import os

from chipbench.peaks import roofline_share
from chipbench.work import sweep_work

_spec = importlib.util.spec_from_file_location(
    "chipbench_metric_sweep_kernel_ms",
    os.path.join(os.path.dirname(__file__), "sweep.kernel_ms.py"))
_kernel_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernel_ms)


def read(run):
    if run.config["engine"] != "explicit":
        return None
    ms = _kernel_ms.read(run)
    if ms is None:
        return None
    r = run.config["n_vectors"][run.traffic["dataset"]]
    flops, nbytes = sweep_work(run.config, run.n, r)
    return roofline_share(flops, nbytes, ms * 1e-3, run.peaks)
