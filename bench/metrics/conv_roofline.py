"""The conv kernels' (2-D and temporal) share of their roofline."""

from roofline import share


def read(run):
    return share(run, "conv")
