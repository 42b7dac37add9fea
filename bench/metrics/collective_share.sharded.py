"""Share of the traced window in which a chip ran a collective (the halo
exchanges' collective-permutes, the moment merge's all-gather), mean
over the cell's chips, in %.  Nothing to read without a trace."""


def read(run):
    tr = run.trace
    if tr is None or not tr.get("collective_s"):
        return None
    coll = sum(tr["collective_s"]) / len(tr["collective_s"])
    return 100.0 * coll / tr["window_s"]
