"""Device time per step of the backward pass outside the head, the
gather and the recomputed forward: ops whose op_name carries
``transpose(`` (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "bwd_ms")
