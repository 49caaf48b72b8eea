"""Process start to the first timed request (host clock), every cell."""


def read(run):
    return run.setup_s
