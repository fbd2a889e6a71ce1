"""Host milliseconds per traced job in ``run_gpic``'s front door: the
config checks, ``validate_features`` with its two blocking reads, and
``row_reorder`` when it is on (the program's front-door span)."""
from chipbench import spans


def read(run):
    names = spans.names()
    if names is None:
        return None
    return spans.ms_per_job(run, names.FRONT_DOOR)
