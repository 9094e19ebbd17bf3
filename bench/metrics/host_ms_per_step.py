"""Mean host time per runtime step: the benchmark's ``step`` span in the
trace, less the device-busy time inside it (padding, result conversion,
runtime bookkeeping, dispatch)."""

import numpy as np


def read(run):
    if run.trace is None or not run.trace.steps:
        return None
    return 1e3 * float(np.mean([span - busy for span, busy in run.trace.steps]))
