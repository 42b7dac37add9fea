"""The chip benchmark: one command runs one cell once (see ``run.py``).

Everything that belongs to one configuration, one traffic mix, one
per-layer metric, one program entry or one stage of a graph is a file of
its own, found by the name that ``BENCHMARK.json`` or the cell's files
give it (``manifest.py`` lists where each name leads).
"""
