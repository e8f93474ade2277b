"""Mean wall time of a batch call outside its round-1 and round-2 fetch
rounds: planning, decoding, the combine and its compiles, verification
and merging."""

import numpy as np


def read(rec):
    if not rec.spans:
        return None
    return float(np.mean([s.end - s.start - s.round1_s - s.round2_s
                          for s in rec.spans])) * 1e3
