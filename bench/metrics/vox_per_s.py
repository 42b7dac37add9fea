"""Input voxels of every call completed in the window over the window's
seconds, in Gvox/s; the window closes when the last call's result is
read.  Nothing to read where the loop counts no voxels."""


def read(run):
    w = run.window
    if "voxels" not in w:
        return None
    return w["voxels"] / w["window_s"] / 1e9
