"""halo_ms.closed: Milliseconds per batch of collective operations on the
device trace, averaged over the chips."""
from bench import readers


def read(m):
    return readers.halo_ms(m)
