"""The simulator's use of a refresh's ranks, in ms: the program's
``hermes.rekey`` spans (the rank scatter into the rank column and, where
the policy's ranks hang on time or the call is a bucket tick, the rebuild
of every waiting queue, ``ClusterSim._refresh_ranks``), summed over the
traced window and divided by their count.  None where the run has no
trace or no such span."""


def read(rec):
    s = (rec.get("trace") or {}).get("spans", {}).get("hermes.rekey")
    if not s or not s["n"]:
        return None
    return s["s"] / s["n"] * 1e3
