"""Device time per step of the scope ``head``: logits and cross-entropy,
forward and backward (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "head_ms")
