"""An array output kept on the device, ready as the caller reads it,
compared block by block with the reference: max |out − ref| over
max |ref|.  Three answers drawn from the seed and the last are kept."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

KEEP_ALL = False
TINY = float(np.finfo(np.float32).tiny)


def finish(out):
    return jax.block_until_ready(out)


@jax.jit
def _block_gap(out, ref):
    return (jnp.max(jnp.abs(out.astype(jnp.float32) - ref)),
            jnp.max(jnp.abs(ref)))


def array_error(out_blocks, ref_blocks) -> float:
    """max |out − ref| over max |ref|, over pairs of blocks; infinite
    where a block's shape differs or a value is not finite."""
    gaps = []
    for o, r in zip(out_blocks, ref_blocks):
        if tuple(o.shape) != tuple(r.shape):
            return math.inf
        gaps.append(_block_gap(jax.device_put(o, r.devices().pop()), r))
    gaps = jax.device_get(gaps)
    diff = max(float(d) for d, _ in gaps)
    scale = max(max(float(s) for _, s in gaps), TINY)
    err = diff / scale
    return err if math.isfinite(err) else math.inf


def errors(loop, kept, control: bool = False) -> dict:
    err = 0.0
    for j, out in kept:
        args = (loop.cfg, loop.shape, loop.keys[j], loop.graph, loop.pad)
        refs = [r for _, _, r in reference.array_blocks(
            *args, devices=loop.devices)]
        if control:
            outs = [r for _, _, r in reference.array_blocks(
                *args, jnp.bfloat16, devices=loop.devices)]
        else:
            n = refs[0].shape[0]
            outs = [out[z0:z0 + n] for z0 in range(0, out.shape[0], n)]
            if len(outs) != len(refs):
                return {"array_err": math.inf}
        err = max(err, array_error(outs, refs))
    return {"array_err": err}
