"""Compiles for a described TPU v5e chip; no chip is attached.

The Pallas kernels of the main path at gpt2-paper-1b widths, and the
gpt2-paper-1b ``init_state`` program with every optimizer-state group in
pinned_host, must pass the TPU compiler and fit one chip's HBM.  The
topology is described inside a fixture, never while a module is imported,
so only the worker that runs these tests loads the TPU library.  (The
whole gpt2-paper-1b train step compiles too, in about three minutes on a
CPU host: too slow for this suite; ``chip_smoke.py`` runs it on the chip.
Its smoke widths compile in seconds, and show the ADAM loop's schedule.)
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# the most one program may use of a v5e chip's 16 GB of HBM, as the
# compiler reports it
HBM_LIMIT_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def rt_1b(topo):
    """gpt2-paper-1b on a one-chip mesh, optimizer state all on host."""
    from repro.configs import get_config, model_class
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("gpt2-paper-1b")
    return ChunkedRuntime(model_class(cfg), cfg, mesh,
                          RuntimeOptions(os_host_fraction=1.0))


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def test_chunked_adam_compiles_at_layer_store(rt_1b, one_chip):
    from repro.kernels.chunked_adam import chunked_adam_kernel

    n = int(np.prod(rt_1b.layouts["layers"].store_shape))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    f32 = sds((n,), jnp.float32)
    c = chunked_adam_kernel.lower(
        f32, f32, f32, sds((n,), jnp.bfloat16), lr=1e-3, beta1=0.9,
        beta2=0.95, eps=1e-8, weight_decay=0.0,
        bias_corr1=sds((), jnp.float32), bias_corr2=sds((), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in c.as_text()
    assert _hbm_bytes(c) <= HBM_LIMIT_BYTES


def test_flash_attention_compiles_at_model_width(one_chip):
    from repro.kernels.flash_attention import flash_attention_kernel

    q = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    c = flash_attention_kernel.lower(q, q, q, causal=True).compile()
    assert "tpu_custom_call" in c.as_text()
    assert _hbm_bytes(c) <= HBM_LIMIT_BYTES


def test_init_state_fits_one_chip_with_state_on_host(rt_1b):
    from repro.runtime import driver

    key = jax.ShapeDtypeStruct(
        (), jax.eval_shape(lambda: jax.random.key(0)).dtype,
        sharding=NamedSharding(rt_1b.mesh, P()))
    c = driver.build_init_state(rt_1b).lower(key).compile()
    assert _hbm_bytes(c) <= HBM_LIMIT_BYTES
    # the fp32 p32/m/v stores come out in pinned_host, not HBM
    _, os_sh = c.output_shardings
    assert {os_sh[n][k]["host"].memory_kind
            for n in os_sh for k in os_sh[n]} == {"pinned_host"}
    n_elems = sum(int(np.prod(s.shape)) for s in rt_1b.store_specs().values())
    assert c.memory_analysis().host_output_size_in_bytes >= 3 * 4 * n_elems


def _adam_loop_transfers(text):
    """Host transfers of the scheduled ADAM while bodies of an executable's
    text, in schedule order: ("in" | "out", "start" | "done", start name)
    for host->HBM dynamic slices and HBM->host dynamic update slices."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name, comps[head.group(1)] = head.group(1), []
        elif name:
            comps[name].append(line)
    bodies = [m.group(1) for body in comps.values() for line in body
              if " while(" in line and "/adam/while" in line
              for m in [re.search(r"body=%([^,\s]+)", line)]]
    out = []
    for body in bodies:
        seq = []
        for line in comps[body]:
            m = re.match(r"\s*%(\S+) = .*? (dynamic-(?:update-)?slice)-"
                         r"(start|done)\((%[^,)]+)", line)
            if m is None:
                continue
            op, phase = m.group(2), m.group(3)
            start = m.group(1) if phase == "start" else m.group(4)[1:]
            if phase == "start" and "S(5)" not in line[:m.start(2)]:
                continue  # no operand in host memory (space 5)
            seq.append(("out" if "update" in op else "in", phase, start))
        starts = {s for _, phase, s in seq if phase == "start"}
        out.append([t for t in seq if t[2] in starts])
    return out


def test_adam_loop_overlaps_host_reads_and_writes(topo):
    """gpt2-paper-1b at smoke widths and four layers (a loop of one
    iteration is unrolled), all optimizer state on host: in the scheduled
    ADAM loop a host->HBM slice is in flight while an HBM->host slice is
    (the next slice is fetched while this one is spilled), and the step
    fits one chip."""
    from repro.configs import get_config, model_class
    from repro.configs.base import InputShape
    from repro.runtime import driver
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("gpt2-paper-1b", smoke=True).replace(num_layers=4)
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh,
                        RuntimeOptions(os_host_fraction=1.0))
    jf, args, _ = driver.build_train_step(rt, InputShape("t", 32, 2, "train"))
    c = jf.lower(*args).compile()
    assert _hbm_bytes(c) <= HBM_LIMIT_BYTES
    loops = _adam_loop_transfers(c.as_text())
    assert loops and all(loops)

    def interval(seq, direction, start):
        at = [i for i, t in enumerate(seq) if t[0] == direction and t[2] == start]
        return at[0], at[-1]

    overlapped = False
    for seq in loops:
        reads = [interval(seq, "in", s) for d, p, s in seq if d == "in" and p == "start"]
        writes = [interval(seq, "out", s) for d, p, s in seq if d == "out" and p == "start"]
        overlapped |= any(r0 < w1 and r1 > w0
                          for r0, r1 in reads for w0, w1 in writes)
    assert overlapped, loops
