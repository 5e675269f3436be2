"""Wall time of the window's micro-batches that hold a full refresh tick,
over their count."""


def read(rec):
    t = rec["tick_batches"]
    return float(t.sum()) / len(t) * 1e3 if len(t) else None
