"""99th percentile, over every micro-batch of the window, of one loop
iteration's wall time (drain, handlers, refresh, spawns, reschedule)."""
import numpy as np


def read(rec):
    b = rec["batches"]
    return float(np.percentile(b, 99)) * 1e3 if len(b) else None
