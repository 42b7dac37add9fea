"""The graph's least HBM bytes (input read once, output written once)
times the calls in the traced window, over the chips' HBM peak, over the
device's busy time, in %.  It counts the graph's work whatever kernels
implement it, so it cannot pass 100%."""


def read(run):
    tr = run.trace
    if tr is None or "calls" not in run.window:
        return None
    busy = sum(tr["busy_s"]) / len(tr["busy_s"])
    if busy <= 0:
        return None
    least_s = (run.least_bytes_per_call * run.window["calls"]
               / (run.peak["hbm_bytes_per_s"] * run.chips))
    return 100.0 * least_s / busy
