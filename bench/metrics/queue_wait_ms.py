"""Mean time from a request's due time to the start of the
runtime step that answered it: queueing in ``ServeRuntime`` plus the
steps it waited behind."""

import numpy as np


def read(run):
    waits = [r.step_start - r.due for r in run.records if r.step_start is not None]
    return 1e3 * float(np.mean(waits)) if waits else None
