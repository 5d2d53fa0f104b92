"""Device time per step of the scope ``gather``: ZeRO all-gather, the
flat store's reshape and unflatten, the replicated-grad sync, their
recomputation in the backward, and their transposes, which scatter the
gradient back into the store (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "gather_ms")
