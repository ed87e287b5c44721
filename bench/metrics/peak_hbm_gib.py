"""Peak device memory in use on the fullest chip, read after the window."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
