"""Reduction of a JAX profiler trace to device busy time and its breakdown.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane ``/device:TPU:<id>``; its line ``XLA Ops`` holds one
event per HLO operation run, named by the operation's HLO text
(``%fusion.12 = bf16[8,1024]{...} fusion(...)``), with its start and
duration in nanoseconds.  A ``while`` op's event spans the ops of its
body, which have events of their own.  The host's threads are lines of
the plane ``/host:CPU``.

Busy time is the union of the intervals of a device's operations, less
the ``while``, ``call`` and ``conditional`` ops that contain others (their
events span the whole body, gaps inside it too); the idle share is
1 - busy / window.  Time by kind of op leaves out the same containers.  An op whose HLO
text names host memory (memory space ``S(5)``) moves data between host
and device.  Gaps between operations are named by the innermost host
event that spans the gap's middle.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = {"while", "call", "conditional"}
HOST_MEMORY = "S(5)"


class Op(NamedTuple):
    name: str  # HLO instruction name: "fusion.12"
    start: float  # ns
    end: float
    host: bool  # an operand or result lives in host memory
    container: bool  # a while, call or conditional: its body ops have events


def op_of(text: str, start: float, end: float) -> Op:
    name = re.match(r"%?([^\s=]+)", text).group(1)
    container = _family(name) in CONTAINERS
    return Op(name, start, end, HOST_MEMORY in text and not container, container)


@dataclasses.dataclass
class Trace:
    ops: dict  # device id -> [Op], sorted by start
    host: list  # [(name, start_ns, end_ns)] of every host thread

    def busy_ns(self, dev: int) -> float:
        total, reach = 0.0, float("-inf")
        for _, s, e, _, container in self.ops[dev]:
            if container or e <= reach:
                continue
            total += e - max(s, reach)
            reach = e
        return total

    def busy_s(self) -> float:
        """Busy seconds, averaged over the traced devices."""
        return sum(self.busy_ns(d) for d in self.ops) / len(self.ops) / 1e9

    def op_time_ns(self, pattern: str | None = None, host: bool = False) -> float:
        """Summed device time of the ops (containers left out) whose name
        matches ``pattern``, or that touch host memory, averaged over the
        devices."""
        rx = re.compile(pattern) if pattern else None
        tot = 0.0
        for evs in self.ops.values():
            tot += sum(o.end - o.start for o in evs if not o.container and (
                (rx is not None and rx.search(o.name)) or (host and o.host)))
        return tot / len(self.ops)

    def gaps(self, dev: int) -> list:
        """[(start_ns, end_ns)] between the device's busy intervals."""
        out, reach = [], None
        for _, s, e, _, container in self.ops[dev]:
            if container:
                continue
            if reach is not None and s > reach:
                out.append((reach, s))
            reach = e if reach is None else max(reach, e)
        return out

    def breakdown(self, top: int = 10) -> dict:
        per_op = collections.Counter()
        for evs in self.ops.values():
            for o in evs:
                if not o.container:
                    per_op[_family(o.name)] += (o.end - o.start) / 1e9 / len(self.ops)
        dev = min(self.ops)
        gaps = sorted(self.gaps(dev), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, t] for n, t in per_op.most_common(top)],
                "idle_gaps": [[self.host_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in gaps]}

    def host_at(self, t: float) -> str:
        spans = [(e - s, n) for n, s, e in self.host if s <= t <= e]
        return min(spans)[1] if spans else "no host event"


def _family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: the op kind, without XLA's numbering."""
    return re.sub(r"[._]\d+$", "", name)


def load(directory: pathlib.Path, devices: list[int]) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    ops, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in devices:
            evs = [op_of(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            ops[int(m.group(1))] = sorted(evs, key=lambda o: o.start)
        elif plane.name == "/host:CPU":
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines for ev in line.events]
    if not ops or not any(ops.values()):
        raise ValueError(f"the trace in {directory} has no device operations")
    return Trace(ops, host)
