"""The host's rank consumption after a refresh dispatch, in ms: the
program's ``hermes.key`` spans (the slot gather and the policy's key over
the store's columns, ``HermesScheduler._ranks_from_store``), summed over
the traced window and divided by their count, one a dispatch.  None where
the run has no trace or the program writes no such span."""


def read(rec):
    s = (rec.get("trace") or {}).get("spans", {}).get("hermes.key")
    if not s or not s["n"]:
        return None
    return s["s"] / s["n"] * 1e3
