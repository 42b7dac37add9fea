"""Seconds of backend compile in the program's plans' first dispatches;
where the persistent compilation cache hits, the time to retrieve the
executable (the sum of ``compile/backend_s``).

Read from ``repro.obs.REGISTRY`` when the reader runs, not from the run
record: what the program owned since the process started.  The cells
build no plan in the window, so this is set-up's; a compile in the
window would count too, as set-up paid late.  Nothing to read where the
program keeps no such metric."""


def read(run):
    from repro.obs import REGISTRY

    m = REGISTRY.snapshot().get("compile/backend_s")
    return None if m is None else m["total"]
