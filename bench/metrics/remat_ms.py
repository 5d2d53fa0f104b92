"""Device time per step of the forward that the backward recomputes
(full remat of the layer scan): ops under ``rematted_computation``,
their gathers left out (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "remat_ms")
