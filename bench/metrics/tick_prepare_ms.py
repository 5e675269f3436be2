"""The prepare phase of a bucket tick's refresh dispatch, in ms: the
program's ``hermes.tick.prepare`` spans (host work up to the enqueue:
the scheduler's dirty-set drain, the row gather, every upload), summed
over the traced window and divided by its ``hermes.tick.wait`` spans,
one a dispatch."""
from bench.trace_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "tick", "prepare")
