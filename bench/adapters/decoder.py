"""How a ``decoder`` configuration runs on the program under test.

Maps the configuration file to the program's model config, and hooks the
benchmark's weights (``bench.reference.decoder.init_*``) into the
program's own initialisation, so ``driver.init_state`` packs them into its
chunk stores by its normal path.  The reference names its leaves as the
program's model does, so one tree of names serves both.
"""

from __future__ import annotations

from bench import weights
from bench.reference import decoder as ref

_ACT = {"gelu_pytorch_tanh": "gelu", "silu": "silu"}
# the program's norms have fixed epsilons
_NORM = {"layernorm": ("ln", 1e-5), "rmsnorm": ("rms", 1e-6)}


def program_config(name: str, cfg: dict):
    from repro.configs.base import BaseConfig

    norm, eps = _NORM[cfg["norm"]]
    if cfg["norm_eps"] != eps:
        raise ValueError(f"{name}: the program's {cfg['norm']} takes eps {eps}, "
                         f"the configuration states {cfg['norm_eps']}")
    if not cfg["tie_word_embeddings"]:
        raise ValueError(f"{name}: this family ties its output head")
    return BaseConfig(
        name=name, arch_type="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], use_rope=True, rope_theta=cfg["rope_theta"],
        activation=_ACT[cfg["hidden_act"]], gated_mlp=cfg["gated_mlp"],
        norm=norm, tie_embeddings=True,
        param_dtype="bfloat16", compute_dtype="bfloat16")


def install_weights(rt, cfg: dict) -> None:
    """Make ``weights.init_state(rt, seed)`` draw the benchmark's weights
    for ``seed``."""
    weights.install(rt, ref, cfg)
