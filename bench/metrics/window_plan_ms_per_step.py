"""Mean time per runtime step spent planning the Brute-L window of
``list`` and ``topk`` batches (``serve.window_plan``: a whole planner
round trip, packing and fetch included), over every step."""

from bench import progspans


def read(run):
    ms = progspans.mean_per_step(run, lambda s: s.stage("serve.window_plan"))
    return None if ms is None else 1e3 * ms
