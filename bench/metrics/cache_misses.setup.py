"""Executables the program's plans compiled and wrote to the persistent
compilation cache because it did not hold them (the counter
``compile/cache_misses``).

Read from ``repro.obs.REGISTRY`` when the reader runs, not from the run
record: what the program owned since the process started.  The cells
build no plan in the window, so this is set-up's; a compile in the
window would count too, as set-up paid late.  Nothing to read where the
program keeps no such metric."""


def read(run):
    from repro.obs import REGISTRY

    m = REGISTRY.snapshot().get("compile/cache_misses")
    return None if m is None else float(m)
