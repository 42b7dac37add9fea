"""Megabytes that one call's halo exchanges send from each chip, as the
sharded program worked them out when it was built (the gauge
``shard/halo_bytes`` in ``repro.obs.REGISTRY``).  Nothing to read where
the program keeps no such gauge."""


def read(run):
    from repro.obs import REGISTRY

    v = REGISTRY.snapshot().get("shard/halo_bytes")
    return None if v is None else v / 1e6
