"""Milliseconds per sweep in which a collective ran on a chip and nothing
else did: the exchange between chips that compute does not hide. The
chip that waits most."""
from chipbench import trace


def read(run):
    ops = run.trace.device_ops
    if run.chips < 2 or not run.sweeps or not ops:
        return None
    if not any(trace.is_collective(e) for o in ops.values() for e in o):
        return None
    exposed = [trace.exposed_collective(o, run.lo, run.hi)
               for o in ops.values()]
    return 1e3 * max(exposed) / run.sweeps
