"""Provider requests that succeeded, per input row, from the session's
execution reports (optimizer and scheduler: fusion, batching, overflow
splits)."""


def read(rec):
    return rec["requests"] / rec["rows"] if rec["rows"] else None
