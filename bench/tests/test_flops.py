"""Model FLOPs per token against hand counts."""

import importlib
import json
import pathlib

import pytest

from bench import flops

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_gpt2_paper_1b():
    # per layer: q, k, v, o 4 * 2048^2 + MLP 2 * 2048 * 8192 = 50,331,648;
    # 20 layers + the tied head 50304 * 2048 = 1,109,655,552 weights
    n = 20 * (4 * 2048**2 + 2 * 2048 * 8192) + 50304 * 2048
    assert flops.matmul_params(load("gpt2-paper-1b")) == n == 1_109_655_552
    # 6N + 12 * layers * 2048 * seq 1024
    want = 6 * n + 12 * 20 * 2048 * 1024
    assert flops.train_flops_per_token(load("gpt2-paper-1b"), 1024) == want
    assert abs(want - 7.16e9) < 0.01e9


def test_qwen2_5_3b_four_layers():
    # q 2048x2048, k and v 2048x256 each, o 2048x2048, SwiGLU 3 x 2048x11008
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    n = 4 * per_layer + 151936 * 2048
    assert flops.matmul_params(load("qwen2.5-3b")) == n
    want = 6 * n + 12 * 4 * 16 * 128 * 2048
    assert flops.train_flops_per_token(load("qwen2.5-3b"), 2048) == want
    # the head is about half of the matrix weights at four layers
    assert 0.45 < 151936 * 2048 / n < 0.55


# today's counts, by hand as above: the family's count is the one mfu reads
HAND = {"gpt2-paper-1b": (1024, 6 * (20 * (4 * 2048**2 + 2 * 2048 * 8192) + 50304 * 2048)
                          + 12 * 20 * 2048 * 1024),
        "qwen2.5-3b": (2048, 6 * (4 * (2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008)
                                  + 151936 * 2048) + 12 * 4 * 16 * 128 * 2048)}


@pytest.mark.parametrize("name", sorted(HAND))
def test_the_family_counts_the_committed_configs_as_before(name):
    cfg = load(name)
    fam = importlib.import_module(f"bench.reference.{cfg['family']}")
    seq, want = HAND[name]
    assert fam.flops_per_token(cfg, seq) == want
