"""Process start to the end of warm-up: building the weights and the KB on
the device, compiling (or loading) every program, one warm-up serve."""


def read(run):
    return run.setup_s
