"""Process start to the first timed request or step, compiles included."""


def read(run):
    return run.setup_s
