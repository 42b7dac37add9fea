"""``sharded_pipe_fn`` of the traffic's graph over the loop's mesh
(``loop.mesh``, axis ``"data"``): the graph is built on a
``jax.ShapeDtypeStruct`` of the volume and runs with the traffic's
``pad_value`` and method ``auto``, jitted once per process.  Its
compiles are counted as the program's (``repro.runtime.compile_cache``
owner ``shard``), as a plan's first dispatch counts its own."""
import functools


def build(loop):
    from repro.runtime import compile_cache

    f = _executor(loop.mesh, tuple(loop.shape), loop.cfg["dtype"],
                  tuple((op, tuple(sorted(kw.items())))
                        for op, kw in loop.graph), loop.pad)

    def call(x):
        with compile_cache.owned("shard"):
            return f(x)

    return call


@functools.lru_cache(maxsize=None)
def _executor(mesh, shape, dtype, graph, pad):
    import jax

    from repro.core.distributed import sharded_pipe_fn
    from repro.pipe import pipe

    p = pipe(jax.ShapeDtypeStruct(shape, dtype))
    for op, kw in graph:
        p = getattr(p, op)(**dict(kw))
    return jax.jit(sharded_pipe_fn(mesh, "data", p, pad_value=pad))
