"""Mean time a served request waited in the frontend's queue before its
micro-batch was dispatched (`FrontendStats.queue_wait_s`)."""

import numpy as np


def read(rec):
    waits = rec.frontend.queue_wait_s
    return float(np.mean(waits)) * 1e3 if waits else None
