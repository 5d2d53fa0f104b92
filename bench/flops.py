"""Model FLOPs of training, per token.

The operations the forward and backward passes require, with nothing
recomputed (full remat's second forward pass does not count): 6 per
weight of every matrix product (2 forward, 4 backward), the output head
included, plus causal attention's score and value products at
12 * layers * (heads * head_dim) * seq per token.  Attention is counted
over the whole sequence, not halved for the causal mask, as PaLM's
model-FLOPs utilisation counts it (Chowdhery et al., 2022, appendix B).
Norms, biases, the softmax and the embedding lookup are left out.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    mlp = (3 if cfg["gated_mlp"] else 2) * d * f
    per_layer = d * qd + 2 * d * kvd + qd * d + mlp
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    return float(6 * matmul_params(cfg) + attn)
