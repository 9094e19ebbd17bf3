"""Mean host time per runtime step spent checking and converting results
into Python answers (``serve.unpack``)."""

from bench import progspans


def read(run):
    ms = progspans.mean_per_step(run, lambda s: s.stage("serve.unpack"))
    return None if ms is None else 1e3 * ms
