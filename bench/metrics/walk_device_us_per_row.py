"""Device time of the walk kernel in the traced window over the rows the
window's refresh dispatches walked."""

# the walk kernel is the refresh path's one Pallas kernel: its device
# operations are the custom calls of target tpu_custom_call
KERNEL_NAMES = ("tpu_custom_call",)


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec["rows_walked"]:
        return None
    s = sum(v for k, v in tr["op_s"].items()
            if any(n in k for n in KERNEL_NAMES))
    return s / rec["rows_walked"] * 1e6 if s > 0 else None
