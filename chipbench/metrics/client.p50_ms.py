"""Median latency of the requests due in the traced window, each from its
due time to its answer; a failed request counts at the drain limit. A
per-layer reading: on this cell the median swings by a fifth from run to
run, too widely to bound end to end."""

import numpy as np


def read(rec):
    return float(np.percentile(rec.latencies_s(), 50, method="higher")) * 1e3
