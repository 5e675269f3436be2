"""The wait phase of a bucket tick's refresh dispatch, in ms: the program's
``hermes.tick.wait`` spans (every read of the dispatch's results),
summed over the traced window and divided by its ``hermes.tick.wait``
spans, one a dispatch."""
from bench.trace_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "tick", "wait")
