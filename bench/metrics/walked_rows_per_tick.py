"""Rows walked by the full ticks' refresh dispatches, over full ticks."""


def read(rec):
    r = rec["tick_rows"]
    return float(r.sum()) / len(r) if len(r) else None
