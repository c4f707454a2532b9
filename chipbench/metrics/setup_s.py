"""Process start to window start: imports, weights, compiling (or reading
the compile cache) and warm-up."""


def read(run):
    return run.setup_s
