"""The attention kernels' (flash: spatial, cross, text; temporal) share
of their roofline."""

from roofline import share


def read(run):
    return share(run, "attention")
