"""Process start to the first timed micro-batch."""


def read(rec):
    return rec["setup_s"]
