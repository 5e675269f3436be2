"""Reduction of one profiler trace (``jax.profiler`` xplane) to the numbers
the per-layer metrics read.

* the window: the host span ``bench.window`` the harness writes around it;
* device busy time: the union of the intervals of the device's operations
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to the
  window and averaged over the devices; idle = window - busy;
* per-operation device time, summed by operation name;
* each idle gap of the device, labelled by the innermost harness span
  (``bench.<name>``) on the host that covers the gap's midpoint, summed by
  label (``engine`` where no span covers it: the simulator's own loop).
"""
from __future__ import annotations

import bisect
import glob
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

SPAN = "bench."
WINDOW = SPAN + "window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_TARGET = "tpu_custom_call"
TOP = 10
NEST_SCAN = 16


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The sub-intervals of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def label_gaps(gap_list: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of idle device time per host span (innermost at the gap's
    midpoint); ``engine`` where none covers it."""
    spans = sorted(spans)
    starts = [sp[0] for sp in spans]
    out: Dict[str, float] = {}
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        best: Optional[Tuple[float, str]] = None
        # spans nest a few deep at most: the covering ones are among the
        # last few that start before the midpoint
        i = bisect.bisect_right(starts, mid)
        for ss, se, name in spans[max(i - NEST_SCAN, 0):i]:
            if se >= mid and (best is None or se - ss < best[0]):
                best = (se - ss, name)
        label = best[1] if best else "engine"
        out[label] = out.get(label, 0.0) + (e - s) * 1e-9
    return out


def reduce_events(device: Dict[str, List[Tuple[str, float, float]]],
                  host: List[Tuple[str, float, float]]) -> Dict:
    """``device``: plane -> [(op name, start ns, duration ns)];
    ``host``: [(span name, start ns, duration ns)] of the harness spans."""
    win = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    spans = [(s, s + d, n[len(SPAN):]) for n, s, d in host
             if n.startswith(SPAN) and n != WINDOW]
    op_ns: Dict[str, float] = {}
    busy, idle_by = [], {}
    n_ops = 0
    for _plane, evs in sorted(device.items()):
        iv = clip([(s, s + d) for _, s, d in evs], lo, hi)
        busy.append(union_ns(iv))
        for name, s, d in evs:
            if s + d > lo and s < hi:
                n_ops += 1
                op_ns[name] = op_ns.get(name, 0.0) + \
                    (min(s + d, hi) - max(s, lo))
        for k, v in label_gaps(gaps(iv, lo, hi), spans).items():
            idle_by[k] = idle_by.get(k, 0.0) + v / len(device)
    window_s = (hi - lo) * 1e-9
    busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": window_s, "busy_s": busy_s, "n_ops": n_ops,
            "op_s": {k: v * 1e-9 for k, v in op_ns.items()},
            "breakdown": {"device_ops": [[k, v * 1e-9] for k, v in ops],
                          "idle_gaps": [[k, v] for k, v in idle]}}


def op_label(op: str, module: str) -> str:
    """A short name for one device operation: its program (the jitted
    function, without the fingerprint), its HLO instruction, and the
    custom-call target of a Pallas kernel."""
    inst = op.split(" = ", 1)[0].lstrip("%")
    mod = module.split("(", 1)[0]
    kern = f" {KERNEL_TARGET}" if f'"{KERNEL_TARGET}"' in op else ""
    return f"{mod}/{inst}{kern}"


def label_ops(ops, modules):
    """Label each op (name, start, duration) by the program running it."""
    mods = sorted((s, s + d, n) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
        out.append((op_label(name, mod), s, d))
    return out


def read_xplane(path: str):
    """(device events per plane, harness host spans) of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    (ops if line.name == OPS_LINE else mods).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
            device[plane.name] = label_ops(ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(SPAN))
    return device, host


def reduce_dir(trace_dir) -> Dict:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise ValueError(f"no xplane trace under {trace_dir}")
    device, host = read_xplane(files[-1])
    if not device:
        raise ValueError("the trace holds no device plane")
    return reduce_events(device, host)
