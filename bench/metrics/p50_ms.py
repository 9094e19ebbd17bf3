"""Median request latency over every answered request of the window: from
when it was due to the end of the step that answered it."""

import numpy as np


def read(run):
    lat = [r.latency for r in run.records if r.latency is not None]
    return 1e3 * float(np.percentile(lat, 50)) if lat else None
