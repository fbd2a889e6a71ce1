"""Share of the roofline the explicit build reaches: the least time for
writing A once at its stated dtype (``work.build_work``) over the affinity
kernel's time per job."""
import importlib.util
import os

from chipbench.peaks import roofline_share
from chipbench.work import build_work

_spec = importlib.util.spec_from_file_location(
    "chipbench_metric_build_kernel_ms",
    os.path.join(os.path.dirname(__file__), "build.kernel_ms.py"))
_kernel_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernel_ms)


def read(run):
    if run.config["engine"] != "explicit":
        return None
    ms = _kernel_ms.read(run)
    if ms is None:
        return None
    flops, nbytes = build_work(run.config, run.n)
    return roofline_share(flops, nbytes, ms * 1e-3, run.peaks)
