"""Keys of the benchmark's weights, drawn from ``--seed``.

The stem and every layer draw from keys of their own, so one layer's
weights can be made again alone: by the program's initialisation, by the
check of its state and by the reference.  Every leaf is drawn from
``weight_key(seed)``, the key the program folds its model rank (0 on
every mesh here) into.  The program draws a leaf it replicates over model
ranks (a norm scale, a router) from the unfolded key instead;
:func:`init_state` has it draw that leaf too from the folded one.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key for any whole number of up to 63 bits."""
    if not 0 <= seed < 2**63:
        raise ValueError(f"--seed {seed} is outside [0, 2**63)")
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, seed // 2**32)


def weight_key(seed: int):
    return jax.random.fold_in(base_key(seed), 0)


def stem_key(key):
    return jax.random.fold_in(key, 0)


def layer_keys(key, n_layers: int):
    """[n_layers] keys; layer l's is ``layer_keys(key, n)[l]``."""
    k = jax.random.fold_in(key, 1)
    return jax.vmap(lambda l: jax.random.fold_in(k, l))(
        jnp.arange(n_layers, dtype=jnp.uint32))


def group_keys(key, groups):
    """{group name: [length] keys} for ``groups``, [(name, length)] in
    model order: layer l of the model, counted over all groups in order,
    draws ``layer_keys(key, total)[l]``, so no two layers share a key and
    a model of one group draws ``layer_keys(key, n)``."""
    keys = layer_keys(key, sum(n for _, n in groups))
    out, lo = {}, 0
    for name, n in groups:
        out[name] = keys[lo:lo + n]
        lo += n
    return out


def install(rt, fam, cfg: dict) -> None:
    """Make the program's initialisation draw the benchmark's weights:
    the stem from ``fam.init_stem``, and each layer of each program group
    from ``fam.init_layer`` of that group, on its :func:`group_keys`.  The
    program's groups must be the family's (``harness.check_groups``); the
    trees must match leaf for leaf."""
    model = rt.model
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_specs())
    groups = model.groups
    model.param_keys = lambda key: (stem_key(key), group_keys(key, fam.groups(cfg)))
    model.init_stem = lambda key: fam.init_stem(cfg, key)
    model.groups = lambda: [
        dataclasses.replace(g, init_layer=lambda key, _n=g.name: fam.init_layer(
            cfg, key, group=_n)) for g in groups()]
    got = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_specs())
    if got != want:
        raise ValueError("the reference's parameter tree differs from the "
                         f"program's:\n{got}\n{want}")


def init_state(rt, seed: int):
    """``driver.init_state`` of a runtime with the benchmark's weights
    installed, every leaf drawn from ``weight_key(seed)``: while it is
    traced, every leaf counts as one the program splits over model ranks,
    so none is drawn from the unfolded key."""
    from repro.runtime import driver

    if rt.ctx.tp != 1:
        raise ValueError("the benchmark's weights are drawn for one model rank")
    axes = rt.tp_axes
    rt.tp_axes = jax.tree.map(lambda _: 0, axes, is_leaf=lambda x: x is None)
    try:
        return driver.init_state(rt, base_key(seed))
    finally:
        rt.tp_axes = axes
