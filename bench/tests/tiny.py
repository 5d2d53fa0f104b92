"""A tiny copy of the benchmark's cells, for the harness's CPU tests."""

import json
import pathlib

TINY = {
    "gpt2": {"family": "decoder", "source": "test", "num_hidden_layers": 2,
             "hidden_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 512,
             "vocab_size": 512, "hidden_act": "gelu_pytorch_tanh",
             "gated_mlp": False, "qkv_bias": False, "norm": "layernorm",
             "norm_eps": 1e-5, "rope_theta": 10000.0, "tie_word_embeddings": True},
    "qwen": {"family": "decoder", "source": "test", "num_hidden_layers": 2,
             "hidden_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
             "vocab_size": 512, "hidden_act": "silu", "gated_mlp": True,
             "qkv_bias": True, "norm": "rmsnorm", "norm_eps": 1e-6,
             "rope_theta": 1e6, "tie_word_embeddings": True},
}
# between the program's readings at this size (CPU, lr 1e-6, two seeds:
# loss 2.4e-4, grad 1.7e-3, change 2.2e-3, moments 1.5e-2) and those of
# half the batch left out (grad 0.55 or more) and of the lower-precision
# control (change 0.93 or more); a state left unchanged reads change 1
LIMITS = {"loss_gap": 0.002, "grad_gap": 0.01, "change_gap": 0.1,
          "moment_gap": 0.1, "cast_gap": 0.0, "init_gap": 0.0}


def traffic(host_fraction: float) -> dict:
    return {"rows": 4, "seq": 32, "dp": 1, "os_host_fraction": host_fraction,
            "remat": "full", "gather_policy": "layer",
            "optimizer": {"lr": 1e-6, "betas": [0.9, 0.95], "eps": 1e-8},
            "corpus": {"zipf": 1.1, "motif_len": 8, "n_motifs": 16,
                       "motif_prob": 0.3},
            "checked_steps": 3}


def make_root(root: pathlib.Path) -> pathlib.Path:
    """Write a BENCHMARK.json with cells ``gpt2.offload`` and ``qwen.hbm``
    and their files under ``root``."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "limits").mkdir()
    spec = {"configs": [], "workloads": [],
            "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
    for (name, cfg), (traffic_name, frac) in zip(
            TINY.items(), (("offload", 1.0), ("hbm", 0.0))):
        f = root / "bench" / "configs" / f"{name}.json"
        f.write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "file": str(f.relative_to(root))})
        (root / "bench" / "traffic" / f"{traffic_name}.json").write_text(
            json.dumps(traffic(frac)))
        cell = f"{name}.{traffic_name}"
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": traffic_name, "chips": 1})
        (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
