"""The verdict: every limit needs its number; a number without a limit
is shown and not compared."""

import math

import pytest

from bench import compare


def test_verdict():
    nums = {"loss_gap": (1e-5, ""), "grad_gap": (0.05, "stem.embed.table")}
    ok, lines = compare.verdict(nums, {"loss_gap": 1e-4})
    assert ok and "grad_gap 0.05 not compared" in lines
    ok, _ = compare.verdict(nums, {"loss_gap": 1e-6})
    assert not ok
    ok, _ = compare.verdict(nums, {"loss_gap": 1e-4, "moment_gap": 1.0})
    assert not ok  # a limit without its number
    ok, _ = compare.verdict({"loss_gap": (math.nan, "")}, {"loss_gap": 1.0})
    assert not ok


def test_block_gaps_leave_out_the_stem():
    want = {"loss": [1.0], "g1": {"stem.embed.table": 2.0, "layers.0.w": 1.0,
                                  "layers.1.w": 1.0},
            "dp": {}, "m": {}, "v": {}}
    want["dp"] = want["m"] = want["v"] = want["g1"]
    got = dict(want, loss=[1.0], g1=dict(want["g1"], **{"stem.embed.table": 1.8}))
    got["m"] = got["v"] = got["dp"] = got["g1"]
    numbers, _ = compare.gaps(got, want)
    assert numbers["grad_gap"][1] == "stem.embed.table"
    assert numbers["grad_gap"][0] == pytest.approx(0.1)
    assert numbers["moment_gap"][0] == pytest.approx(0.1)
    assert numbers["grad_gap_blocks"][0] == 0.0
    assert numbers["moment_gap_blocks"][0] == 0.0
