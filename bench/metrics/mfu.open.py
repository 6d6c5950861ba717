"""mfu.open: Per cent of the chips' peak: the least time of the whole model's
work over chips times the traced window."""
from bench import readers


def read(m):
    return readers.mfu(m)
