"""Keys of the benchmark's weights, drawn from ``--seed``.

The stem and every layer draw from keys of their own, so one layer's
weights can be made again alone: by the program's initialisation, by the
check of its state and by the reference.  The harness hands the program
``base_key(seed)``; the program folds its model rank (0 on every mesh
here) into the key of each tensor-parallel leaf, and every such leaf is a
random one, so the weights' key is ``weight_key(seed)``.  Leaves drawn
from the unfolded key are constants (norm scales, biases).
"""

from __future__ import annotations

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
