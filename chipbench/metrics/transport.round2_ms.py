"""Mean wall time of a batch's round-2 (document) fetches, as the
program's `QueryStats.docs.elapsed_s` reports it; one sample a batch."""

import numpy as np


def read(rec):
    if not rec.spans:
        return None
    return float(np.mean([s.round2_s for s in rec.spans])) * 1e3
