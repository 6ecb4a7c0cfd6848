"""Percent of the HBM roofline reached by the k-way merge kernel: the least
bytes of a merge (each key read and written once) at peak bandwidth, over
the summed device time of its operations in the trace."""

from bench.roofline import kernel_share


def read(run):
    return kernel_share(run, "kway_merge")
