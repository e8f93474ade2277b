"""Mean wall time of one batch call into the service layer, timed by the
benchmark's adapter around it."""

import numpy as np


def read(rec):
    if not rec.spans:
        return None
    return float(np.mean([s.end - s.start for s in rec.spans])) * 1e3
