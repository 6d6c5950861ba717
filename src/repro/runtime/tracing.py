"""Named spans of the serving path, on the JAX profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``.
While a trace is captured (``jax.profiler.trace(dir)`` or
``start_trace``/``stop_trace``), each span lands on the host plane of the
same ``.xplane.pb`` as the device's operations, on one clock, with its
counters as event stats; spans opened inside another span on the same
thread nest under it. With no trace captured a span costs about a
microsecond and records nothing: the profiler is the only switch.

Rules the call sites keep: spans open on the host only, never inside
jitted code, and a counter is a size or an id already at hand, never
something computed for the span's sake. ``SPANS`` declares every name the
program emits, with its layer and counters.

    with jax.profiler.trace("/tmp/trace"):
        server.submit(...)
        server.drain()
"""
from __future__ import annotations

from typing import Dict

import jax

PREFIX = "repro."

#: name -> "layer: what it covers [counters]".
SPANS: Dict[str, str] = {
    "server.drain": "Server: one Server.drain, every pending request "
                    "[pending]",
    "server.batch": "Server: one micro-batch, collect to responses "
                    "[batch = index, size, request = first id, level]",
    "server.price": "Server: Session.account on a pricing-cache miss "
                    "(simulation.simulate) [batch_size]",
    "collect": "Collect: Session.collect, one request's upload round trip "
               "[rows]",
    "daq.quantize": "Collect: daq_pack's bit assignment and row "
                    "quantization, plus the byte shuffle where the lossless "
                    "stage follows (uniform_pack too) [rows]",
    "daq.lossless": "Server: lossless_compress of the shuffled payload, "
                    "sizing the wire bytes pricing charges (under "
                    "server.price; never in a collect) [in_bytes]",
    "daq.dequantize": "Collect: daq_unpack [rows]",
    "execute": "Executor: Session.execute / execute_many [batch_size]",
    "execute.dispatch": "Executor: backend entry until the jitted call "
                        "returns: operands, host-to-device copies, dispatch "
                        "[upload_bytes]",
    "execute.wait": "Executor: block_until_ready on the result",
    "execute.download": "Executor: device-to-host copy and unstack "
                        "[download_bytes]",
}


def span(name: str, **counters: int) -> jax.profiler.TraceAnnotation:
    """The span ``repro.<name>`` carrying ``counters`` as event stats; a
    counter known only at the end goes in with ``.set_metadata(...)`` on
    the span before it closes."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counters)
