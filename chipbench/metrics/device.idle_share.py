"""Share of the traced window in which no operation ran on the chip, in %:
1 - busy union / window. On several chips, the idlest."""
from chipbench import trace


def read(run):
    window = run.hi - run.lo
    ops = run.trace.device_ops
    if window <= 0 or not ops:
        return None
    busy = [trace.length(trace.busy(o, run.lo, run.hi)) for o in ops.values()]
    if max(busy) <= 0:
        return None
    return 100.0 * (1.0 - min(busy) / window)
