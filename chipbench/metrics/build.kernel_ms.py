"""Device milliseconds of the graph build per job: the affinity kernel
(explicit engine: A and its degrees in one pass) or the streaming degree
pass, in the traced window over the traced jobs, averaged over chips."""

#: ops of the build kernels in the trace (kernels/affinity.py,
#: kernels/streaming.py), named by the jitted functions that make them
BUILD_KERNEL = {"explicit": "affinity_and_degree",
                "streaming": "affinity_degree_streaming"}


def read(run):
    per_chip = run.kernel_seconds(BUILD_KERNEL[run.config["engine"]])
    if not run.jobs or not per_chip or min(per_chip) <= 0:
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / len(run.jobs)
