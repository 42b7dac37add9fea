"""Seconds the tile autotuner spent measuring its candidates, its own
compiles included, one entry per tuned key (the sum of
``tune/measure_s``).

Read from ``repro.obs.REGISTRY`` when the reader runs, not from the run
record: what the program owned since the process started.  The cells
build no plan in the window, so this is set-up's; a compile in the
window would count too, as set-up paid late.  Nothing to read where the
program keeps no such metric."""


def read(run):
    from repro.obs import REGISTRY

    m = REGISTRY.snapshot().get("tune/measure_s")
    return None if m is None else m["total"]
