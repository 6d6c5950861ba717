"""execute_ms.open: Milliseconds per batch of Session.execute_many (dispatch,
upload, device, download), from the benchmark's span."""
from bench import readers


def read(m):
    return readers.span_ms(m, "execute")
