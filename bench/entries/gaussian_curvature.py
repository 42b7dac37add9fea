"""``filters.gaussian_curvature`` of the raw volume, with the traffic's
``pad_value``."""


def build(loop):
    from repro.core import filters

    pad = loop.pad
    return lambda x: filters.gaussian_curvature(x, pad_value=pad)
