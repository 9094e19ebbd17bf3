"""95th percentile of how late the load generator submitted a request
after it was due (a starved generator would read as a fast server)."""

import numpy as np


def read(run):
    lags = [r.sent - r.due for r in run.records]
    return 1e3 * float(np.percentile(lags, 95)) if lags else None
