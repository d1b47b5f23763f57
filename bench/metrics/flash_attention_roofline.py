"""Kernels: ``flash_attention``'s share of its roofline in the traced chunk
(moves ``served_rps``)."""
from bench.roofline import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash_attention")
