"""server_ms.open: Server's own milliseconds per batch: the drain's wall time
less the collect and execute spans inside it (admission, batching, pricing
on the simulated clock)."""
from bench import readers


def read(m):
    return readers.server_ms(m)
