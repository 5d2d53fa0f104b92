"""Tensor-parallel metadata and gradient synchronization.

Each model exposes ``tp_axes()``: a pytree mirroring its param tree whose
leaves are the TP-sharded axis index, or ``None`` for params replicated
across the model axis.

Two uses:

1. **Gradient correctness.**  A replicated param feeds TP-sharded
   branches on every model rank; each rank's autodiff only sees its own
   branch, so the true gradient is the *psum over the model axis* of the
   per-rank gradients.  :func:`sync_replicated_grads` wraps replicated
   leaves in an identity whose VJP is that psum — sharded leaves (whose
   per-rank grads are already complete, and must NOT be mixed) are left
   alone.  Because replicated params receive identical synced grads and
   identical optimizer state on every rank, their copies stay bitwise in
   sync across training.

2. **TP resharding.**  ``split_for_tp`` splits a tp=1 ("global") param
   tree into a rank's local shard — used by tests (tp parity) and by the
   checkpoint converter.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


def _grad_psum(axis_name: str):
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        # psum makes the cotangent invariant over the model axis; the
        # pcast restores the varying type expected for the store-shard
        # input (the value is invariant in fact — all ranks hold the same
        # sum).
        g = jax.lax.psum(g, axis_name)
        return (jax.lax.pcast(g, axis_name, to="varying"),)

    f.defvjp(fwd, bwd)
    return f


def sync_replicated_grads(params: Any, axes: Any, axis_name: str | None, tp: int) -> Any:
    """Wrap replicated leaves so their grads psum over the model axis."""
    if axis_name is None:
        return params
    f = _grad_psum(axis_name)

    def apply(p, ax):
        return f(p) if ax is None else p

    return jax.tree.map(apply, params, axes,
                        is_leaf=lambda x: x is None)


def split_for_tp(tree: Any, axes: Any, tp: int, rank: int) -> Any:
    """Slice a tp=1 param tree into the TP-local shard for ``rank``."""

    def split(p, ax):
        if ax is None:
            return p
        n = p.shape[ax] // tp
        return jax.lax.slice_in_dim(p, rank * n, (rank + 1) * n, axis=ax)

    return jax.tree.map(split, tree, axes, is_leaf=lambda x: x is None)


def infer_tp_axes(global_specs: Any, local_specs: Any, tp: int) -> Any:
    """Derive the axes tree by comparing tp=1 and tp=N leaf shapes."""

    def infer(g, l):
        if g.shape == l.shape:
            return None
        for i, (a, b) in enumerate(zip(g.shape, l.shape)):
            if a == b * tp:
                return i
        raise ValueError(f"cannot infer tp axis: {g.shape} vs {l.shape}")

    return jax.tree.map(infer, global_specs, local_specs)
