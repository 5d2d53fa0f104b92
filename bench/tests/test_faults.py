"""The whole run on the CPU at a tiny size, past the look for a chip:
``correct`` holds on the program as it is and comes out false with the
timed path broken underneath, and for the lower-precision control."""

import time

import pytest

from bench import compare, harness
from bench.reference import decoder, train
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell, fault", [
    ("gpt2.offload", None), ("qwen.hbm", None),
    ("gpt2.offload", "frozen"), ("gpt2.offload", "half_batch")])
def test_correct_only_without_a_fault(root, cell, fault):
    r = harness.run(cell, 2**33 + 7, 0.3, False, t_start=time.perf_counter(),
                    platforms=("cpu",), fault=fault, root=root)
    assert r["correct"] is (fault is None), r["compared"]
    assert r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert {"tokens_per_s", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("name", sorted(tiny.TINY))
def test_lower_precision_control_fails(name):
    cfg = tiny.TINY[name]
    t = tiny.traffic(0.0)
    from bench.corpus import Corpus

    corpus = Corpus(cfg["vocab_size"], 5, **t["corpus"])
    batches = [corpus.batch(t["rows"], t["seq"], k) for k in range(3)]
    want = train.train3(decoder, cfg, t["optimizer"], 5, batches)
    got = train.train3(decoder, cfg, t["optimizer"], 5, batches, lower=True)
    numbers, _ = compare.gaps(got, want)
    ok, lines = compare.verdict(numbers, {k: v for k, v in tiny.LIMITS.items()
                                          if k in numbers})
    assert not ok, lines
