"""95th percentile of the latency of every request due in the window,
each from its due time to its answer; a failed request counts at the
drain limit. A tail over all requests, not a median of chunks."""

import numpy as np


def read(rec):
    return float(np.percentile(rec.latencies_s(), 95, method="higher")) * 1e3
