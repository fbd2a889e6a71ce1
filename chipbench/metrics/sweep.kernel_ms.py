"""Device milliseconds of one power sweep: the time of the engine's sweep
kernel in the traced window over the sweeps the traced jobs ran, averaged
over the chips (each chip sweeps its own stripe)."""

#: the op of each engine's sweep kernel in the trace: a Pallas call is
#: named by the jitted function that makes it (kernels/power_step.py
#: ``degree_normalized_matmat``, kernels/streaming.py ``affinity_matmat``)
SWEEP_KERNEL = {"explicit": "degree_normalized_matmat",
                "streaming": "affinity_matmat"}


def read(run):
    per_chip = run.kernel_seconds(SWEEP_KERNEL[run.config["engine"]])
    if not run.sweeps or not per_chip or min(per_chip) <= 0:
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.sweeps
