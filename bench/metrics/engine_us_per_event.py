"""Window wall time outside the rank refresh (``ClusterSim.policy_time``
counts the refresh) per event drained."""


def read(rec):
    if not rec["events"]:
        return None
    return (rec["window_s"] - rec["policy_s"]) / rec["events"] * 1e6
