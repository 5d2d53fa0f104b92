"""A family and adapter for the harness's tests only: a decoder whose
first layer is dense and whose others are mixtures of experts, with an
untied output head, as the program's ``MoELM`` runs it with standard
attention and ``first_dense_layers`` 1.

Two layer groups, as the program names them: ``dense_layers`` (the
``decoder`` family's block) and ``moe_layers`` (attention, then a router's
softmax over ``n_routed_experts``, the top ``num_experts_per_tok``
experts' SwiGLU outputs weighted by their probabilities, renormalised
where ``norm_topk_prob``, plus ``n_shared_experts`` as one dense SwiGLU).
Every expert is computed for every token: no token is dropped, as the
program drops none while its capacity holds every assignment.

The tests register this one module as ``bench.reference.tiny_moe`` and
``bench.adapters.tiny_moe``, so nothing of it ships with the benchmark.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import decoder

CONFIG = {"family": "tiny_moe", "source": "test", "num_hidden_layers": 3,
          "first_k_dense_replace": 1, "hidden_size": 128, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
          "moe_intermediate_size": 64, "n_routed_experts": 4, "num_experts_per_tok": 4,
          "n_shared_experts": 1, "norm_topk_prob": True, "capacity_factor": 1.0,
          "router_aux_coef": 0.0, "vocab_size": 512, "hidden_act": "silu",
          "gated_mlp": True, "qkv_bias": False, "norm": "rmsnorm", "norm_eps": 1e-6,
          "rope_theta": 1e4, "tie_word_embeddings": False}

CONFIG_KEYS = decoder.CONFIG_KEYS | {
    "first_k_dense_replace", "moe_intermediate_size", "n_routed_experts",
    "num_experts_per_tok", "n_shared_experts", "norm_topk_prob"}


def groups(cfg: dict) -> list[tuple[str, int]]:
    k = cfg["first_k_dense_replace"]
    return [("dense_layers", k), ("moe_layers", cfg["num_hidden_layers"] - k)]


def flops_per_token(cfg: dict, seq: int) -> float:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    attn = 2 * d * qd + 2 * d * kvd
    ffn = 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    moe = d * cfg["n_routed_experts"] + 3 * d * cfg["moe_intermediate_size"] * experts
    weights_ = n * attn + k * ffn + (n - k) * moe + cfg["vocab_size"] * d
    return float(6 * weights_ + 12 * n * qd * seq)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def init_stem(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    k1, k2 = jax.random.split(key)
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    stem = decoder.init_stem(cfg, k1, dtype)
    stem["unembed"] = {"table": decoder._normal(k2, (vocab, d), d, dtype)}
    return stem


def init_layer(cfg: dict, key, dtype=jnp.bfloat16, *, group: str) -> dict:
    k1, k2 = jax.random.split(key)
    p = decoder.init_layer(cfg, k1, dtype)
    if group == "dense_layers":
        return p
    del p["mlp"]
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = f * cfg["n_shared_experts"]
    ks = jax.random.split(k2, 7)
    normal = lambda k, shape, fan_in: decoder._normal(k, shape, fan_in, dtype)
    # the program keeps its router in float32 and stores it as bf16
    router = decoder._normal(ks[0], (d, e), d, dtype).astype(jnp.float32)
    p["moe"] = {"router": router,
                "w_gate": normal(ks[1], (e, d, f), d),
                "w_up": normal(ks[2], (e, d, f), d),
                "w_down": normal(ks[3], (e, f, d), f),
                "shared": {"w_gate": normal(ks[4], (d, fs), d),
                           "w_up": normal(ks[5], (d, fs), d),
                           "w_down": normal(ks[6], (fs, d), fs)}}
    return p


def moe(cfg: dict, m: dict, h, q=lambda t: t):
    """-> (the experts' output for the normed stream h [B, S, d], aux)."""
    e, top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", q(h), q(m["router"])), axis=-1)
    p_top, idx = jax.lax.top_k(probs, top)
    if cfg["norm_topk_prob"]:
        p_top = p_top / jnp.sum(p_top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, e) * p_top[..., None], axis=-2)  # [B, S, e]
    act = jax.nn.silu
    g = jnp.einsum("bsd,edf->bsef", q(h), q(m["w_gate"]))
    u = jnp.einsum("bsd,edf->bsef", q(h), q(m["w_up"]))
    out = jnp.einsum("bsef,efd->bsed", q(act(g) * u), q(m["w_down"]))
    y = jnp.einsum("bsed,bse->bsd", out, gate) + decoder.mlp(cfg, m["shared"], h, q)
    # the Switch balance term on each expert's mean probability and its
    # share of first choices, times the configuration's coefficient
    first = jnp.mean(jax.nn.one_hot(idx[..., 0], e), axis=(0, 1))
    aux = cfg["router_aux_coef"] * e * jnp.sum(jnp.mean(probs, axis=(0, 1)) * first)
    return y, aux


def layer(cfg: dict, p: dict, x, q=lambda t: t, *, group: str):
    if group == "dense_layers":
        return decoder.layer(cfg, p, x, q)
    k = decoder.dims(cfg)
    h = decoder._norm(k, x, p["norm_attn"], None)
    x = x + decoder.attention(cfg, p["attn"], h, q)
    y, aux = moe(cfg, p["moe"], decoder._norm(k, x, p["norm_mlp"], None), q)
    return x + y, aux


embed = decoder.embed


def head_loss_sum(cfg: dict, stem: dict, x, labels, q=lambda t: t):
    return decoder.head_loss_sum(cfg, {**stem, "embed": stem["unembed"]}, x, labels, q)


# ---------------------------------------------------------------------------
# the adapter
# ---------------------------------------------------------------------------


def program_config(name: str, cfg: dict):
    from repro.configs.base import MoEConfig

    return MoEConfig(
        name=name, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], qkv_bias=cfg["qkv_bias"], use_rope=True,
        rope_theta=cfg["rope_theta"], activation="silu", gated_mlp=True, norm="rms",
        tie_embeddings=False, n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], n_shared_experts=cfg["n_shared_experts"],
        d_ff_expert=cfg["moe_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        capacity_factor=cfg["capacity_factor"], router_aux_coef=cfg["router_aux_coef"],
        param_dtype="bfloat16", compute_dtype="bfloat16")


def install_weights(rt, cfg: dict) -> None:
    weights.install(rt, sys.modules[__name__], cfg)
