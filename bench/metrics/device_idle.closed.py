"""device_idle.closed: Per cent of the traced window with no operation on the
device, averaged over the chips."""
from bench import readers


def read(m):
    return readers.device_idle(m)
