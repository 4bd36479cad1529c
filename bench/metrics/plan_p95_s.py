"""95th percentile of plan latency over every plan of the window."""

import numpy as np


def read(rec):
    return float(np.percentile(rec["latencies"], 95)) \
        if rec["latencies"] else None
