"""collect_ms.closed: Milliseconds per request of Session.collect, the DAQ
upload round trip, from the benchmark's span."""
from bench import readers


def read(m):
    return readers.span_ms(m, "collect")
