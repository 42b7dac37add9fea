"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

For configs that opt in (``pp_stages > 1``) the layer stack is split into S
stages; microbatches flow through stages with ``shard_map`` + ``ppermute``:
at tick t, stage s computes microbatch (t − s) and passes its activation to
stage s+1 — the classic GPipe schedule with S − 1 bubble ticks on each side.

The production (16,16)/(2,16,16) meshes keep PP off (depth fits via
FSDP+TP), but the substrate exists for deeper models / larger clusters and
is verified against sequential execution in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    mesh: Mesh,
    stage_axis: str,
    layer_fn: Callable,   # (params_one_stage, x_microbatch) -> x_microbatch
    stage_params,         # pytree, leaves with leading dim = n_stages
    x,                    # (n_micro, mb, ...) microbatched input
):
    """Run ``layer_fn`` as an S-stage pipeline.  Returns (n_micro, mb, ...).

    stage_params leaves are sharded (stage, ...); x is replicated.
    """
    S = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    ticks = n_micro + S - 1

    def stage_fn(params, xs):
        params = jax.tree.map(lambda t: t[0], params)  # local stage params
        s = jax.lax.axis_index(stage_axis)

        def tick(carry, t):
            buf_in, outputs = carry
            # stage 0 injects microbatch t (when valid); others use buf_in
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = xs[mb_idx]
            cur = jnp.where(s == 0, inject, buf_in)
            y = layer_fn(params, cur)
            # pass to next stage; last stage's output is collected
            buf_next = jax.lax.ppermute(
                y, stage_axis, perm=[(i, i + 1) for i in range(S - 1)])
            out_idx = t - (S - 1)
            valid = (out_idx >= 0) & (s == S - 1)
            outputs = jax.lax.cond(
                valid.any() if hasattr(valid, "any") else valid,
                lambda o: o.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y),
                lambda o: o,
                outputs,
            )
            return (buf_next, outputs), None

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        outputs = jax.lax.psum(
            jnp.where(s == S - 1, outputs, jnp.zeros_like(outputs)),
            stage_axis)
        return outputs

    spec_p = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def sequential_reference(layer_fn, stage_params, x):
    """What the pipeline must equal: stages applied in order."""
    S = jax.tree.leaves(stage_params)[0].shape[0]

    def apply_all(xmb):
        for s in range(S):
            p = jax.tree.map(lambda t: t[s], stage_params)
            xmb = layer_fn(p, xmb)
        return xmb

    return jax.vmap(apply_all)(x)
