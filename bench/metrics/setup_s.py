"""Process start to the first timed request: imports, device start-up, the
access plan, weights drawn from the seed, and compiling (or loading from
the compile cache) and warming up the served steps."""


def read(run):
    return run.setup_s
