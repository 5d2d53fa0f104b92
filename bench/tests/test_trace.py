"""The trace reduction on a synthetic trace with known intervals."""

import pytest

from bench.metrics import host_copy_ms, idle_share
from bench.trace import Trace, op_of

MS = 1_000_000  # ns


def two_devices():
    # device 0: a while over [0,18) holding [0,10) fusion and [5,15)
    # all-gather, a gap, [20,30) a copy from host memory
    # device 1: [0,30) one op
    ops = {0: [op_of("%while.4 = (f32[8]{0:S(5)}) while(...)", 0, 18 * MS),
               op_of("%fusion.1 = f32[8]{0} fusion(%a)", 0, 10 * MS),
               op_of("%all-gather.3 = f32[32]{0} all-gather(%b)", 5 * MS, 15 * MS),
               op_of("%copy-start.2 = (f32[8]{0:S(5)}, f32[8]{0}) copy-start(%p)",
                     20 * MS, 30 * MS)],
           1: [op_of("%fusion.1 = f32[8]{0} fusion(%a)", 0, 30 * MS)]}
    host = [("train_step", 0, 40 * MS), ("device_put", 14 * MS, 19 * MS)]
    return Trace(ops, host)


def test_busy_is_the_union_of_intervals():
    t = two_devices()
    assert t.busy_ns(0) == 25 * MS  # [0,15) and [20,30): the while's tail is idle
    assert t.busy_ns(1) == 30 * MS
    assert t.busy_s() == pytest.approx(27.5e-3)


def test_gaps_and_what_the_host_did():
    t = two_devices()
    assert t.gaps(0) == [(15 * MS, 20 * MS)]
    bd = t.breakdown()
    assert bd["idle_gaps"] == [["device_put", 5e-3]]
    kinds = dict(bd["device_ops"])
    assert kinds["fusion"] == pytest.approx((10 + 30) / 2 * 1e-3)
    assert kinds["all-gather"] == pytest.approx(5e-3)


def test_op_time():
    t = two_devices()
    assert t.op_time_ns(pattern="all-gather") == pytest.approx(10 * MS / 2)
    # the while's text names host memory, but it only holds other ops
    assert t.op_time_ns(host=True) == pytest.approx(5 * MS)


def test_idle_share_and_host_copies():
    class Run:
        trace = two_devices()
        window_s = 40e-3
        steps = 2
    assert idle_share.read(Run) == pytest.approx(100 * (1 - 27.5 / 40))
    assert host_copy_ms.read(Run) == pytest.approx(10 / 2 / 2)


def test_no_trace_gives_no_metric():
    class Run:
        trace = None
    assert idle_share.read(Run) is None
    assert host_copy_ms.read(Run) is None
