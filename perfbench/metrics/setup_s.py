"""Process start to the first timed request: imports, data from the
seed, the bulk load and warm-up (compiles or cache reads)."""


def read(run):
    return run.setup_s
