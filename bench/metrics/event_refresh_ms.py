"""Mean wall time of the window's event-path rank refreshes
(``ClusterSim._refresh_ranks`` called with an id list)."""


def read(rec):
    t = rec["event_refresh_s"]
    return float(t.mean()) * 1e3 if len(t) else None
