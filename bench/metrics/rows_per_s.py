"""Input rows of every plan of the window over the time from the first
plan's start to the last plan's end (host clock)."""


def read(rec):
    if not rec["plans"] or rec["window_s"] <= 0:
        return None
    return rec["rows"] / rec["window_s"]
