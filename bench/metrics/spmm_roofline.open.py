"""spmm_roofline.open: Per cent of the SpMM kernels' roofline: the least time
of the neighbour-sums over the kernels' device time."""
from bench import readers


def read(m):
    return readers.spmm_roofline(m)
