"""Mean device-busy time per runtime step: the union of device operations
inside each ``step`` span (planner and executor programs)."""

import numpy as np


def read(run):
    if run.trace is None or not run.trace.steps:
        return None
    return 1e3 * float(np.mean([busy for _, busy in run.trace.steps]))
