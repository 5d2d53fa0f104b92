"""Device time per step of ops in none of the other six classes:
compiler-inserted copies without metadata, and ops outside every scope
and every transform (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "unscoped_ms")
