"""Over ``list`` and ``topk`` batches: rows that the plan assigned to an
engine, over the rows that the executors ran (every executor runs every
padded row: three for ``list``, two for ``topk``)."""

from bench import progspans


def read(run):
    found = progspans.steps(run)
    return None if found is None else progspans.useful_row_share(found)
