"""Device time per step of the train step's layers, by named scope.

The program names its layers with ``jax.named_scope`` (``runtime/step.py``:
``embed``, ``gather``, ``layers``, ``head``, ``adam``).  The compiler keeps
the scope path in each HLO instruction's ``op_name`` metadata, with JAX's
own transform names around it: ``jvp(...)`` in the forward pass,
``transpose(jvp(...))`` in the backward, ``checkpoint/rematted_computation``
in the forward that the backward recomputes.  A trace op
(``bench/trace.py``) is joined to its ``op_name`` through its instruction
name (``fusion.12``), unique in the module, in the text of the timed
executable, and lands in the first class whose rule matches:

  adam_ms      scope ``adam``: the optimizer's slice loop, host copies included
  gather_ms    scope ``gather``: ZeRO's all-gather, store relayouts, and
               their transposes, the gradient's way back into the store
  head_ms      scope ``head``: logits and cross-entropy, forward and backward
  remat_ms     ``rematted_computation``
  bwd_ms       ``transpose(``
  fwd_ms       ``jvp(``, or scope ``embed`` or ``layers``
  unscoped_ms  anything else, instructions without metadata included

A scope matches a whole component of the path or the innermost name of a
transform wrapper (``transpose(jvp(head))``), never part of another name.
The last component names the primitive, and is no scope: ``jnp.take``'s
primitive is ``gather`` too.  Container ops (``while``, ``call``,
``conditional``) are left out, as in ``Trace.op_time_ns``, so the seven
add up to the summed time of every other op.
"""

from __future__ import annotations

import re

CLASSES = ("adam_ms", "gather_ms", "head_ms", "remat_ms", "bwd_ms", "fwd_ms",
           "unscoped_ms")
PROGRAM_SCOPES = {"embed", "gather", "layers", "head", "adam"}

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"',
                          re.MULTILINE)
_WRAPPER = re.compile(r"^\w+\((.*)\)$")


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of every instruction of the module text
    that carries an ``op_name``."""
    return dict(_INSTRUCTION.findall(hlo_text))


def scopes(op_name: str) -> set:
    """The scope names on the path of ``op_name``, transform wrappers
    unwrapped, the primitive left out."""
    out = set()
    for part in op_name.split("/")[:-1]:
        while m := _WRAPPER.match(part):
            part = m.group(1)
        out.add(part)
    return out


def classify(op_name: str | None) -> str:
    """The class (one of ``CLASSES``) of an instruction with this op_name."""
    if not op_name:
        return "unscoped_ms"
    path, found = op_name.rpartition("/")[0], scopes(op_name)
    if "adam" in found:
        return "adam_ms"
    if "gather" in found:
        return "gather_ms"
    if "head" in found:
        return "head_ms"
    if "rematted_computation" in found:
        return "remat_ms"
    if "transpose(" in path:
        return "bwd_ms"
    if "jvp(" in path or found & {"embed", "layers"}:
        return "fwd_ms"
    return "unscoped_ms"


def ms_per_step(trace, names: dict, steps: int) -> dict:
    """{class: device ms per step} of the trace's ops, containers left out,
    averaged over its devices; ``names`` maps instruction to op_name."""
    cls = {name: classify(op) for name, op in names.items()}
    total = dict.fromkeys(CLASSES, 0.0)
    for evs in trace.ops.values():
        for o in evs:
            if not o.container:
                total[cls.get(o.name, "unscoped_ms")] += o.end - o.start
    return {k: v / len(trace.ops) / steps / 1e6 for k, v in total.items()}


def read(run, metric: str):
    """``metric`` (one of ``CLASSES``) of a traced run, or None without a
    trace or where the timed executable carries none of the program's
    scopes.  The module text is parsed once a run: the seven readers share
    the result, kept on the run."""
    if run.trace is None:
        return None
    got = getattr(run, "_scope_ms", None)
    if got is None:
        names = op_names(run.compiled.as_text())
        named = any(scopes(op) & PROGRAM_SCOPES for op in names.values())
        got = run._scope_ms = ms_per_step(run.trace, names, run.steps) if named else {}
    return got.get(metric)
