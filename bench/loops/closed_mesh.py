"""A closed loop over a mesh: as ``closed``, but each pool volume is laid
out over a 1-D mesh ``"data"`` of the cell's chips, sharded on its
slice axis, and each chip makes its own slices from the seed, so no chip
ever holds the volume.  The entry finds the mesh as ``loop.mesh``.
"""
import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import data, manifest

AXIS = "data"

closed = manifest.module("loops", "closed")


class Loop(closed.Loop):
    def make_data(self):
        n = len(self.devices)
        if self.shape[0] % n:
            raise ValueError(f"{self.shape[0]} slices do not split over "
                             f"{n} chips")
        self.mesh = Mesh(np.array(self.devices), (AXIS,))
        sharding = NamedSharding(self.mesh, P(AXIS))
        per = self.shape[0] // n
        self.pool = [jax.make_array_from_single_device_arrays(
            self.shape, sharding,
            [data.make_rows(self.cfg, self.shape, k, i * per, per, device=d)
             for i, d in enumerate(self.devices)])
            for k in self.keys]
        jax.block_until_ready(self.pool)
        self.call = self.entry.build(self)
