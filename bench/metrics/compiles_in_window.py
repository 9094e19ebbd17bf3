"""Programs the service compiled during the window (its
``compile_counts``): warm-up should leave none."""


def read(run):
    return run.compiles
