"""Mean time per runtime step from an endpoint program's call until every
output is a host array (``serve.run`` outside a window plan): dispatch,
device time and the fetches."""

from bench import progspans


def read(run):
    ms = progspans.mean_per_step(run, lambda s: s.stage("serve.run"))
    return None if ms is None else 1e3 * ms
