"""The comparison that decides ``correct`` for a training cell.

Both sides, the program and the reference (or the control put in the
program's place), are reduced to the same summary of the first three
training steps:

* ``loss``: the loss of each step;
* ``g1``: the norm of each leaf's gradient at step 1 (the program's is
  read from its fp32 Adam state after step 1: m = (1 - beta1) * g);
* ``dp``: the norm of each leaf's change of the fp32 master copy p32
  over the three steps, as step 4 would read it;
* ``m``, ``v``: the norm of each leaf's fp32 Adam moments after step 3;
* ``cast``: the program's largest gap between its bf16 working copy and
  the bf16 cast of its own p32 (0 where the copy is that cast).

A leaf is one parameter tensor of one layer ("layers.3.attn.wq") or of
the stem ("stem.embed.table").  ``grad_gap_blocks`` and
``moment_gap_blocks`` are the gradient and moment gaps over the layers'
leaves alone: the stem's token table takes its gradient through a gather,
whose transpose the program sums over repeated tokens in bf16.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

# leaves whose reference gradient is this small against the median leaf's
# carry no signal (a key bias under softmax): Adam moves them by rounding
# alone, so they are left out of the gradient, change and moment gaps
NULL_GRAD = 1e-3


def leaf_names(tree, prefix: str) -> list[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [prefix + "".join(f".{getattr(k, 'key', getattr(k, 'idx', k))}"
                             for k in path) for path, _ in paths]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


def leaf_norms(tree, prefix: str) -> dict[str, float]:
    """{leaf name: L2 norm} of a parameter tree."""
    vals = jax.device_get(_norms(tree))
    return {n: float(v) for n, v in zip(leaf_names(tree, prefix), vals)}


def _worst_gap(got: dict, want: dict, keep: list[str]) -> tuple[float, str]:
    """Largest |got - want| of a leaf's norm, over max(want's norm of the
    leaf, the median leaf's), with the leaf it was found at."""
    med = statistics.median(want[n] for n in keep)
    worst, at = 0.0, ""
    for n in keep:
        gap = abs(got[n] - want[n]) / max(want[n], med)
        if gap > worst or not at:
            worst, at = gap, n
    return worst, at


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared, from two summaries (see the module doc)."""
    names = sorted(want["g1"])
    if sorted(got["g1"]) != names:
        raise ValueError("the two summaries name different leaves")
    med = statistics.median(want["g1"][n] for n in names)
    keep = [n for n in names if want["g1"][n] >= NULL_GRAD * med]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"]))
    out = {"loss_gap": (loss_gap, "")}
    out["grad_gap"] = _worst_gap(got["g1"], want["g1"], keep)
    out["change_gap"] = _worst_gap(got["dp"], want["dp"], keep)
    m_gap = _worst_gap(got["m"], want["m"], keep)
    v_gap = _worst_gap(got["v"], want["v"], keep)
    out["moment_gap"] = max(m_gap, v_gap)
    blocks = [n for n in keep if not n.startswith("stem.")]
    out["grad_gap_blocks"] = _worst_gap(got["g1"], want["g1"], blocks)
    out["moment_gap_blocks"] = max(_worst_gap(got["m"], want["m"], blocks),
                                   _worst_gap(got["v"], want["v"], blocks))
    if "cast" in got:
        out["cast_gap"] = (got["cast"], "")
    return out, [n for n in names if n not in keep]


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """``correct`` and one line per number, beside its limit.  A limit
    whose number is missing fails; a number the cell sets no limit for is
    shown and not compared."""
    ok, lines = True, []
    for name in sorted(set(numbers) | set(limits)):
        value = numbers.get(name, (float("nan"), ""))[0]
        limit = limits.get(name)
        if limit is None:
            lines.append(f"{name} {value!r} not compared")
            continue
        passed = value == value and value <= limit
        ok &= passed
        lines.append(f"{name} {value!r} limit {limit!r}{'' if passed else '  FAILED'}")
    return ok, lines
