"""Plain reference of a pre-norm decoder-only language model.

Written from the published descriptions: GPT-2 as PatrickStar's Table 2
sizes it (LayerNorm, tanh-approximated GELU, an un-gated MLP) and Qwen2.5
(RMSNorm, SwiGLU, grouped-query attention with biases on q, k and v).
Both take rotary position embeddings on split halves of each head, causal
softmax attention scaled by 1/sqrt(head_dim), a residual stream around
each block, a final norm and an output head tied to the token embedding.
The loss is the mean next-token cross-entropy over every position.

Everything here is float32 ``jax.numpy`` on explicit parameter trees, one
layer at a time; the caller sets ``jax.default_matmul_precision``.  The
benchmark's weights come from :func:`init_stem` and :func:`init_layer`.
``q`` rounds the operands of each matrix product; the plain reference
passes the identity, the lower-precision control a rounding.

A reference family is a module of this package that describes its
configurations' shape to the harness:

* ``CONFIG_KEYS``: the keys a configuration file of the family must hold;
* ``groups(cfg)``: its layer groups in model order, [(name, length)],
  named as the program names its own;
* ``init_stem(cfg, key)``, ``embed(cfg, stem, tokens)`` and
  ``head_loss_sum(cfg, stem, x, labels, q)``;
* ``init_layer(cfg, key, group=...)`` and ``layer(cfg, p, x, q, group=...)``
  for a layer of a named group; a layer returns x, or (x, aux) where aux
  is a scalar term of the objective;
* ``flops_per_token(cfg, seq)``: model FLOPs of training per token.

This family has one group, ``layers``, of ``num_hidden_layers``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import flops

CONFIG_KEYS = {"hidden_size", "intermediate_size", "num_attention_heads",
               "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size"}


def groups(cfg: dict) -> list[tuple[str, int]]:
    return [("layers", cfg["num_hidden_layers"])]


def flops_per_token(cfg: dict, seq: int) -> float:
    return flops.train_flops_per_token(cfg, seq)


def dims(cfg: dict) -> dict:
    """The sizes and choices of one configuration file."""
    return {
        "d": cfg["hidden_size"], "f": cfg["intermediate_size"],
        "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "act": cfg["hidden_act"],
        "norm": cfg["norm"], "eps": cfg["norm_eps"],
        "gated": cfg["gated_mlp"], "bias": cfg["qkv_bias"],
        "theta": cfg["rope_theta"],
    }


# ---------------------------------------------------------------------------
# weights: N(0, 1/fan_in) matrices, unit norm scales, zero biases, bf16
# ---------------------------------------------------------------------------


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def init_stem(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    k = dims(cfg)
    stem = {"embed": {"table": _normal(key, (k["vocab"], k["d"]), k["d"], dtype)},
            "final_norm": jnp.ones((k["d"],), dtype)}
    if k["norm"] == "layernorm":
        stem["final_norm_b"] = jnp.zeros((k["d"],), dtype)
    return stem


def init_layer(cfg: dict, key, dtype=jnp.bfloat16, *, group: str = "layers") -> dict:
    k = dims(cfg)
    d, f, qd, kvd = k["d"], k["f"], k["h"] * k["hd"], k["kv"] * k["hd"]
    ks = jax.random.split(key, 7)
    attn = {"wq": _normal(ks[0], (d, qd), d, dtype),
            "wk": _normal(ks[1], (d, kvd), d, dtype),
            "wv": _normal(ks[2], (d, kvd), d, dtype),
            "wo": _normal(ks[3], (qd, d), qd, dtype)}
    if k["bias"]:
        attn.update(bq=jnp.zeros((qd,), dtype), bk=jnp.zeros((kvd,), dtype),
                    bv=jnp.zeros((kvd,), dtype))
    mlp = {"w_up": _normal(ks[4], (d, f), d, dtype),
           "w_down": _normal(ks[5], (f, d), f, dtype)}
    if k["gated"]:
        mlp["w_gate"] = _normal(ks[6], (d, f), d, dtype)
    p = {"attn": attn, "mlp": mlp,
         "norm_attn": jnp.ones((d,), dtype), "norm_mlp": jnp.ones((d,), dtype)}
    if k["norm"] == "layernorm":
        p["norm_attn_b"] = jnp.zeros((d,), dtype)
        p["norm_mlp_b"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mm(a, b, q):
    return jnp.einsum("...k,kn->...n", q(a), q(b))


def _norm(k, x, w, b):
    if k["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + k["eps"]) * w + b
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + k["eps"]) * w


def _rope(x, theta):
    """x: [B, S, heads, hd]; rotates the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_ACT = {"gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "silu": jax.nn.silu}


def attention(cfg: dict, a: dict, h, q=lambda t: t):
    """Causal self-attention of the normed stream h [B, S, d], before the
    output projection's residual add."""
    k = dims(cfg)
    b, s, _ = h.shape
    qh, kh, vh = (_mm(h, a[w], q) for w in ("wq", "wk", "wv"))
    if k["bias"]:
        qh, kh, vh = qh + a["bq"], kh + a["bk"], vh + a["bv"]
    qh = _rope(qh.reshape(b, s, k["h"], k["hd"]), k["theta"])
    kh = _rope(kh.reshape(b, s, k["kv"], k["hd"]), k["theta"])
    vh = vh.reshape(b, s, k["kv"], k["hd"])
    rep = k["h"] // k["kv"]  # query head i reads key/value head i // rep
    kh, vh = jnp.repeat(kh, rep, axis=2), jnp.repeat(vh, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh)) / math.sqrt(k["hd"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(vh)).reshape(b, s, -1)
    return _mm(o, a["wo"], q)


def mlp(cfg: dict, m: dict, h, q=lambda t: t):
    """The MLP of the normed stream h, gated where ``w_gate`` is given."""
    up = _mm(h, m["w_up"], q)
    act = _ACT[cfg["hidden_act"]]
    hid = act(_mm(h, m["w_gate"], q)) * up if "w_gate" in m else act(up)
    return _mm(hid, m["w_down"], q)


def layer(cfg: dict, p: dict, x, q=lambda t: t, *, group: str = "layers"):
    """One block on the residual stream x [B, S, d] (float32)."""
    k = dims(cfg)
    h = _norm(k, x, p["norm_attn"], p.get("norm_attn_b"))
    x = x + attention(cfg, p["attn"], h, q)
    h = _norm(k, x, p["norm_mlp"], p.get("norm_mlp_b"))
    return x + mlp(cfg, p["mlp"], h, q)


def embed(cfg: dict, stem: dict, tokens):
    return jnp.take(stem["embed"]["table"], tokens, axis=0)


def head_loss_sum(cfg: dict, stem: dict, x, labels, q=lambda t: t):
    """Sum over the positions of x [N, d] of the next-token cross-entropy."""
    k = dims(cfg)
    h = _norm(k, x, stem["final_norm"], stem.get("final_norm_b"))
    logits = _mm(h, stem["embed"]["table"].T, q)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)
