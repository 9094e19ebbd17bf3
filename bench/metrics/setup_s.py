"""Set-up paid by every run: imports, the collection, the index build, the
pattern pool and the warm-up (compiles, or loads from the cache)."""


def read(run):
    return run.setup_s
