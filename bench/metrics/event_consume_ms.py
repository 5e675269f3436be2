"""The consume phase of an event batch's refresh dispatch, in ms: the
program's ``hermes.event.consume`` spans (host work after the reads: the
arena's host mirrors, the scheduler's counters, rank drain, prewarm plan
and rank dict), summed over the traced window and divided by its
``hermes.event.wait`` spans, one a dispatch."""
from bench.trace_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "event", "consume")
