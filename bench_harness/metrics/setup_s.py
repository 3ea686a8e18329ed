"""Process start to the window's start: imports, JAX and chip start-up,
making the data on the device, compiling or loading every program from
the cache, and the warm-up rounds."""


def read(run):
    return run.setup_s
