"""Device time per step of the scope ``adam``: the optimizer's slice
loop, with its loads and stores to and from ``pinned_host``
(``host_copy_ms`` is that part), the update and the bf16 cast
(``bench/scopes.py``)."""

from bench import scopes


def read(run):
    return scopes.read(run, "adam_ms")
