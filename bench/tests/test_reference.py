"""Each reference family at a smoke size against the program's own model
code on the CPU: the same loss and gradients from the same weights, both
sides in float32 at the highest matmul precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.adapters import decoder as adapter
from bench.reference import decoder as ref
from bench.tests.tiny import TINY


def program_loss(cfg, params, tokens, labels):
    from repro.models.layers import AxisCtx
    from repro.models.transformer import TransformerLM

    pcfg = adapter.program_config("tiny", cfg).replace(
        param_dtype="float32", compute_dtype="float32")
    model = TransformerLM(pcfg, AxisCtx())
    (group,) = model.groups()
    batch = {"tokens": tokens, "labels": labels,
             "global_tokens": jnp.float32(tokens.size)}
    x, extras = model.embed(params["stem"], batch)
    for layer in params["layers"]:
        x, _ = group.apply(layer, x, extras, model.ctx)
    return model.head_loss(params["stem"], x, batch)


def reference_loss(cfg, params, tokens, labels):
    x = ref.embed(cfg, params["stem"], tokens)
    for layer in params["layers"]:
        x = ref.layer(cfg, layer, x)
    x = x.reshape(-1, x.shape[-1])
    return ref.head_loss_sum(cfg, params["stem"], x, labels.reshape(-1)) / tokens.size


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_matches_program(name):
    cfg = TINY[name]
    key = weights.weight_key(3)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    keys = weights.layer_keys(key, cfg["num_hidden_layers"])
    params = {"stem": f32(ref.init_stem(cfg, weights.stem_key(key))),
              "layers": [f32(ref.init_layer(cfg, k)) for k in keys]}
    # nonzero biases and norm offsets, so their gradients are exercised
    params = jax.tree.map(
        lambda a: a + 0.01 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 16)).astype(np.int32)
    labels = rng.integers(0, cfg["vocab_size"], (2, 16)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss, argnums=1)(cfg, params, tokens, labels)
        lr, gr = jax.value_and_grad(reference_loss, argnums=1)(cfg, params, tokens, labels)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale + 1e-9, path
