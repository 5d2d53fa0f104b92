"""Device time per step of the forward pass outside the head: ops whose
op_name carries ``jvp(`` or the scope ``embed`` or ``layers``, and none of
the rules before it (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "fwd_ms")
