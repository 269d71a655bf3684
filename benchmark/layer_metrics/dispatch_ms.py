"""The host handing cached programs to the chip: ``dispatch`` spans
(the call of a cached program to its return, with no wait for its
outputs: argument flattening, jax's dispatch path, the enqueue), per
executed query. A program older than the span closes none and reads
0.0, as any phase that closed no span in the window."""

from ._phases import per_query_ms


def read(run):
    return per_query_ms(run, "dispatch")
