"""Kernels: ``expert_gmm``'s share of its roofline in the traced chunk
(moves ``served_rps``)."""
from bench.roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "expert_gmm")
