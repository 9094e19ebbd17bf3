"""8 x the bytes of every live device array after warm-up, over the
collection's symbols (terminators included): the index as it sits on the
device, in the paper's unit."""


def read(run):
    return 8.0 * run.live_bytes / run.n_symbols
