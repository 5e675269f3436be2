"""Host-device crossings per refresh dispatch: the uploads (``h2d``) and
reads (``d2h``) the scheduler's ``refresh_stats`` counted in the window,
over its event and tick dispatches."""


def read(rec):
    c = rec.get("counters") or {}
    n = c.get("event_dispatches", 0) + c.get("tick_dispatches", 0)
    if not n:
        return None
    return (c.get("h2d", 0) + c.get("d2h", 0)) / n
