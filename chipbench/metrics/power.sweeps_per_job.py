"""Sweeps the power loop ran per traced job (``PICResult.n_iter``): the
count the stop rule sets, which every sweep kernel's time multiplies."""


def read(run):
    if not run.jobs:
        return None
    return run.sweeps / len(run.jobs)
