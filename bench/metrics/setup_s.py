"""Seconds from the start of the benchmark process to the instant the
arrival source opens: imports, weights made on the device from the seed,
and the warm-up that compiles (or loads from the cache) and runs every
chunk shape the batcher can form."""


def read(run):
    return run.setup_s
