"""Set-up: process start to the call of the first timed restore (s)."""


def read(run):
    return run.setup_s
