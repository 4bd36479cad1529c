"""Median plan latency: building the Pipeline to collect() returning."""

import numpy as np


def read(rec):
    return float(np.percentile(rec["latencies"], 50)) \
        if rec["latencies"] else None
