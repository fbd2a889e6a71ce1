"""Blocking device-to-host reads ``run_gpic`` makes per traced job: the
program's sync spans inside its call spans."""
from chipbench import spans


def read(run):
    return spans.syncs_per_job(run)
