"""Mean number of requests in a served micro-batch (`FrontendStats`)."""

import numpy as np


def read(rec):
    sizes = rec.frontend.batch_sizes
    return float(np.mean(sizes)) if sizes else None
