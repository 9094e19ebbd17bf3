"""Mean host-device transfers per runtime step: the puts and fetches that
the program counts on ``serve.step`` (``transfers``)."""

from bench import progspans


def read(run):
    return progspans.mean_per_step(run, lambda s: s.attrs.get("transfers", 0))
