"""Mean host time per runtime step outside its endpoint calls
(``serve.step`` less its ``serve.call`` spans): expiry, batch cutting,
retries and answer bookkeeping in ``ServeRuntime``."""

from bench import progspans


def read(run):
    ms = progspans.mean_per_step(run, lambda s: s.seconds - s.calls())
    return None if ms is None else 1e3 * ms
