"""Share of the traced window in which the device ran no operation, mean
over the cell's chips, in %."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    busy = sum(tr["busy_s"]) / len(tr["busy_s"])
    return 100.0 * (1.0 - busy / tr["window_s"])
