"""``Pipe.run`` of the traffic's graph: each stage ``(op, kwargs)`` is the
pipe's method of that name, called with those keyword arguments, and the
graph runs with the traffic's ``pad_value``."""


def build(loop):
    from repro.pipe import pipe

    graph, pad = loop.graph, loop.pad

    def call(x):
        p = pipe(x)
        for op, kw in graph:
            p = getattr(p, op)(**kw)
        return p.run(pad_value=pad)

    return call
