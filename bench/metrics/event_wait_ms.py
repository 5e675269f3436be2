"""The wait phase of an event batch's refresh dispatch, in ms: the
program's ``hermes.event.wait`` spans (every read of the dispatch's
results), summed over the traced window and divided by its
``hermes.event.wait`` spans, one a dispatch."""
from bench.trace_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "event", "wait")
