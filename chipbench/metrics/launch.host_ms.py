"""Host milliseconds per traced job spent enqueueing the jitted program
(the program's launch span): argument handling and dispatch, and any
retrace or recompile."""
from chipbench import spans


def read(run):
    names = spans.names()
    if names is None:
        return None
    return spans.ms_per_job(run, names.LAUNCH)
