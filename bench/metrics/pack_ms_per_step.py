"""Mean host time per runtime step spent packing batches for the device
(``serve.pack``: normalising and padding patterns, the knobs, the puts),
outside a window plan."""

from bench import progspans


def read(run):
    ms = progspans.mean_per_step(run, lambda s: s.stage("serve.pack"))
    return None if ms is None else 1e3 * ms
