"""Percent of the HBM roofline reached by the Alltoallv delivery kernel:
the least bytes of a delivery (each key read and written once) at peak
bandwidth, over the summed device time of its operations in the trace."""

from bench.roofline import kernel_share


def read(run):
    return kernel_share(run, "alltoallv_deliver")
