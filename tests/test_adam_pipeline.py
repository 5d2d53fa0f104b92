"""The pipelined ADAM loop over host-resident optimizer state.

A host part's slices are fetched one ahead of their update
(``runtime.step.stream_slices``); a device part runs the plain
``map_slices`` loop.  On XLA:CPU the host part is an ordinary device
buffer, so these tests run the pipelined loop's structure, and its
arithmetic must equal the plain loop's bit for bit.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, model_class
from repro.launch.mesh import make_smoke_mesh
from repro.runtime import driver
from repro.runtime.step import (ChunkedRuntime, RuntimeOptions, map_slices,
                                stream_slices)


LAYERS, FRACTIONS = (1, 2, 5), (0.5, 1.0)


def _runtime(cfg, fraction):
    return ChunkedRuntime(model_class(cfg), cfg, make_smoke_mesh(1, 1),
                          RuntimeOptions(os_host_fraction=fraction))


def _two_steps(rt):
    """p32/m/v (device and host parts joined) and bf16 params after two
    ADAM updates with fixed gradients."""
    pstores, osstores = driver.init_state(rt, jax.random.key(0))
    grads = {name: jax.random.normal(jax.random.key(i), p.shape, p.dtype)
             for i, (name, p) in enumerate(sorted(pstores.items()))}
    update = jax.jit(rt._adam_update)
    for step in range(2):
        pstores, osstores = update(pstores, osstores, grads, jnp.int32(step))
    state = {(name, "p"): p for name, p in pstores.items()}
    for name, st in osstores.items():
        for k, parts in st.items():
            state[name, k] = jnp.concatenate(
                [parts["dev"], parts["host"]], axis=1 if name == "stem" else 2)
    return state


def _mismatches(layers, fraction):
    """The (store, array) pairs whose state differs between the pipelined
    host loop at ``fraction`` and the plain loop over all slices on the
    device (fraction 0.0), with ``layers`` slices in the layer store."""
    cfg = get_config("gpt2-paper-1b", smoke=True).replace(num_layers=layers)
    want, got = (_two_steps(_runtime(cfg, f)) for f in (0.0, fraction))
    return sorted("/".join(key) for key in want
                  if not bool(jnp.array_equal(got[key], want[key])))


@pytest.fixture(scope="module")
def mismatches():
    """``_mismatches`` of every case, computed in a child process whose
    XLA:CPU targets an instruction set without FMA.  The CPU backend
    contracts a multiply and an add into one FMA wherever a fusion's shape
    lets it, so one arithmetic fused two ways can differ in the last bit;
    without FMA it does the operations as written, and the two loops must
    agree exactly.  The flag is read when the backend starts, so only a
    new process can take it."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
        [os.environ.get("XLA_FLAGS", ""), "--xla_cpu_max_isa=AVX"]),
        PYTHONPATH=os.pathsep.join([src, os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run([sys.executable, __file__], env=env, text=True,
                         capture_output=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    return json.loads(run.stdout.splitlines()[-1])


@pytest.mark.parametrize("fraction", FRACTIONS)
@pytest.mark.parametrize("layers", LAYERS)
def test_pipelined_host_loop_matches_plain_loop(layers, fraction, mismatches):
    """With ``layers`` slices in the layer store, the host part's pipelined
    loop leaves the same p32/m/v and bf16 params after two steps, bit for
    bit, as the plain loop that runs every slice on the device."""
    cfg = get_config("gpt2-paper-1b", smoke=True).replace(num_layers=layers)
    assert _runtime(cfg, fraction).pipelined_slices()["layers"] == layers - 1
    assert mismatches[f"{layers}-{fraction}"] == []


@pytest.mark.parametrize("n", [1, 2, 5])
def test_stream_slices_equals_map_slices(n):
    """Slice by slice, ``stream_slices`` computes what ``map_slices`` does
    with the fetch and spill moved into ``fn``; each slice's update reads
    its own slice of the device store and of ``xs``."""
    key = jax.random.split(jax.random.key(n), 3)
    ints = lambda k: jax.random.randint(k, (1, n, 3, 8), -99, 99)  # exact
    stores = tuple(map(ints, key[:2]))
    outs = (jnp.zeros((1, n, 3, 8), jnp.bfloat16),)
    xs = (ints(key[2]),)

    def fn(a, b, o, x):
        return a * x + b, b - a, (a + b + o).astype(o.dtype)

    want = map_slices(fn, (*stores, *outs), xs)
    got = stream_slices(fn, lambda x: x, lambda y: y, stores, outs, xs)
    for w, g in zip(want, got):
        assert g.shape == w.shape and bool(jnp.array_equal(g, w))


@pytest.mark.parametrize("fraction,ahead", [
    (0.0, {"stem": 0, "layers": 0}),
    (0.5, {"stem": 0, "layers": 1}),
    (1.0, {"stem": 0, "layers": 1}),
])
def test_pipelined_slices_counts_host_fetches_ahead(fraction, ahead):
    """gpt2-paper-1b smoke: two layers, three chunk groups in the layer
    store (two on host at 0.5), one in the stem (on host at 1.0 only, and
    a one-slice loop fetches nothing ahead)."""
    cfg = get_config("gpt2-paper-1b", smoke=True)
    assert _runtime(cfg, fraction).pipelined_slices() == ahead


def test_pipelined_slices_at_published_widths():
    """gpt2-paper-1b, all state on host: 19 of the 20 layer slices are
    fetched while the slice before is updated."""
    cfg = get_config("gpt2-paper-1b")
    assert _runtime(cfg, 1.0).pipelined_slices() == {"stem": 0, "layers": 19}
    assert _runtime(cfg, 0.0).pipelined_slices() == {"stem": 0, "layers": 0}


if __name__ == "__main__":  # the child of the ``mismatches`` fixture
    print(json.dumps({f"{n}-{f}": _mismatches(n, f)
                      for n in LAYERS for f in FRACTIONS}))
