"""Device time by named scope: the classification rule, the reduction on a
synthetic trace, and the scopes in the tiny gpt2 cell's compiled step."""

import pytest

from bench import scopes
from bench.trace import Trace, op_of

MS = 1_000_000  # ns
READERS = ["fwd_ms", "bwd_ms", "remat_ms", "head_ms", "gather_ms", "adam_ms",
           "unscoped_ms"]


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", "fwd_ms"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", "bwd_ms"),
    ("jit(train_step)/jvp()/while/body/closed_call/layers/dot_general", "fwd_ms"),
    ("jit(train_step)/layers/sin", "fwd_ms"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/layers/"
     "...d,df->...f/dot_general", "bwd_ms"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/layers/dot_general", "remat_ms"),
    ("jit(train_step)/adam/while/body/closed_call/div", "adam_ms"),
    ("jit(train_step)/adam/while/body/closed_call/dynamic_update_slice", "adam_ms"),
    ("jit(train_step)/jvp()/while/body/closed_call/gather/all_gather", "gather_ms"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/gather/all_gather", "gather_ms"),
    ("jit(train_step)/transpose(jvp(gather))/reduce_scatter", "gather_ms"),
    ("jit(train_step)/jvp(head)/jit(take_along_axis)/gather", "head_ms"),
    ("jit(train_step)/transpose(jvp(head))/dot_general", "head_ms"),
    ("jit(train_step)/jvp(heads)/dot_general", "fwd_ms"),
    ("jit(train_step)/transpose(jvp(multihead))/dot_general", "bwd_ms"),
    ("jit(train_step)/headroom/add", "unscoped_ms"),
    ("jit(train_step)/psum", "unscoped_ms"),
    ("jit(train_step)/transpose/add", "unscoped_ms"),
    ("reduce_sum", "unscoped_ms"),
    ("", "unscoped_ms"),
    (None, "unscoped_ms"),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/adam/mul"}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="pstores[\\'stem\\']"}
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(embed)/mul" stack_frame_id=3}
  %all-gather.3 = f32[32]{0} all-gather(%fusion.1), metadata={op_type="x" op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/gather/all_gather"}
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(head))/dot_general"}
  %while.4 = f32[8]{0} while(%p), condition=%cond, body=%body, metadata={op_name="jit(train_step)/adam/while"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/adam/while/body/closed_call/div"}
  %copy-start.2 = (f32[8]{0:S(5)}, f32[8]{0}) copy-start(%p)
  ROOT %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/layers/dot_general"}
}
"""


def test_op_names_of_module_text():
    names = scopes.op_names(HLO)
    assert names["fusion.1"] == "jit(train_step)/jvp(embed)/mul"
    assert names["fusion.5"].endswith("/checkpoint/layers/dot_general")
    assert names["multiply.3"] == "jit(train_step)/adam/mul"
    assert names["all-gather.3"].endswith("/gather/all_gather")
    assert "copy-start.2" not in names


def synthetic_run(steps=2):
    # device 0: fwd 10, bwd 4, gather 3, head 2, adam 6 (the while holding it
    # left out), copy 1, an op the module text lacks 1; device 1: fwd 20
    ops = {0: [op_of("%fusion.1 = f32[8]{0} fusion(%p)", 0, 10 * MS),
               op_of("%all-gather.3 = f32[32]{0} all-gather(%a)", 10 * MS, 13 * MS),
               op_of("%fusion.7 = f32[8]{0} fusion(%p)", 13 * MS, 15 * MS),
               op_of("%while.4 = f32[8]{0} while(%p)", 15 * MS, 22 * MS),
               op_of("%fusion.2 = f32[8]{0} fusion(%p)", 15 * MS, 21 * MS),
               op_of("%copy-start.2 = (f32[8]{0:S(5)}, f32[8]{0}) copy-start(%p)",
                     22 * MS, 23 * MS),
               op_of("%fusion.5 = f32[8]{0} fusion(%p)", 23 * MS, 27 * MS),
               op_of("%fusion.99 = f32[8]{0} fusion(%p)", 27 * MS, 28 * MS)],
           1: [op_of("%fusion.1 = f32[8]{0} fusion(%p)", 0, 20 * MS)]}

    class Compiled:
        calls = 0

        def as_text(self):
            Compiled.calls += 1
            return HLO

    class Run:
        trace = Trace(ops, [])
        compiled = Compiled()

    Run.steps = steps
    return Run


def test_seven_classes_sum_to_op_time_per_step():
    run = synthetic_run()
    got = {m: scopes.read(run, m) for m in READERS}
    assert got == pytest.approx({"fwd_ms": 7.5, "bwd_ms": 1.0, "remat_ms": 0.0,
                                 "head_ms": 0.5, "gather_ms": 0.75, "adam_ms": 1.5,
                                 "unscoped_ms": 0.5})
    per_step = run.trace.op_time_ns(pattern=".") / 1e6 / run.steps
    assert sum(got.values()) == pytest.approx(per_step)
    assert run.compiled.calls == 1  # parsed once for all seven


def test_readers_read_their_class():
    import importlib

    run = synthetic_run()
    for m in READERS:
        reader = importlib.import_module(f"bench.metrics.{m}")
        assert reader.read(run) == scopes.read(run, m)


def test_no_trace_or_no_program_scope_gives_nothing():
    run = synthetic_run()
    run.trace = None
    assert scopes.read(run, "fwd_ms") is None
    # a program without the named scopes: transforms alone name no layer
    unnamed = synthetic_run()
    unnamed.compiled.as_text = lambda: HLO.replace("(embed)", "()").replace(
        "(head)", "()").replace("/gather/", "/").replace("/adam/", "/").replace(
        "/layers/", "/")
    assert all(scopes.read(unnamed, m) is None for m in READERS)


@pytest.fixture(scope="module")
def tiny_gpt2_text():
    """The tiny gpt2 cell's train step, built as ``harness.run`` builds it
    and compiled on the CPU."""
    from bench.adapters import decoder as adapter
    from bench.tests import tiny
    from repro.configs import model_class
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_smoke_mesh
    from repro.runtime import driver
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    tr, opt = tiny.traffic(1.0), tiny.traffic(1.0)["optimizer"]
    pcfg = adapter.program_config("gpt2", tiny.TINY["gpt2"])
    rt = ChunkedRuntime(model_class(pcfg), pcfg, make_smoke_mesh(tr["dp"], 1),
                        RuntimeOptions(remat=tr["remat"], gather_policy=tr["gather_policy"],
                                       os_host_fraction=tr["os_host_fraction"],
                                       lr=opt["lr"], betas=tuple(opt["betas"]),
                                       eps=opt["eps"]))
    adapter.install_weights(rt, tiny.TINY["gpt2"])
    jf, specs, _ = driver.build_train_step(rt, InputShape("t", tr["seq"], tr["rows"], "train"))
    return jf.lower(*specs).compile().as_text()


def test_compiled_step_carries_every_scope(tiny_gpt2_text):
    names = scopes.op_names(tiny_gpt2_text).values()
    found = set().union(*map(scopes.scopes, names))
    assert scopes.PROGRAM_SCOPES <= found
    classes = {scopes.classify(n) for n in names}
    assert {"fwd_ms", "bwd_ms", "remat_ms", "head_ms", "gather_ms", "adam_ms"} <= classes
