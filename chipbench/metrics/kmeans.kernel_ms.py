"""Device milliseconds of the k-means assignment kernel per job
(kernels/kmeans_assign.py), averaged over chips."""

#: op of the assignment kernel in the trace (kernels/kmeans_assign.py)
KMEANS_KERNEL = "kmeans_assign"


def read(run):
    per_chip = run.kernel_seconds(KMEANS_KERNEL)
    if not run.jobs or not per_chip or max(per_chip) <= 0:
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / len(run.jobs)
