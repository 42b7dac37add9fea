"""Set-up time: process start to the end of warm-up, compile included."""


def read(run):
    return run.setup_s
