"""Process start to window start, host clock (s)."""


def read(run):
    return run.setup_s
