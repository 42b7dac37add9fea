"""Host-clock seconds of the warm-up calls (after the data is made)."""


def read(run):
    return run.setup_split["warmup_s"]
