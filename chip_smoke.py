#!/usr/bin/env python3
"""On-chip smoke check of the compiled chunked-ZeRO trainer.

  python chip_smoke.py             # one TPU chip: phases `train`, `kernels`
  python chip_smoke.py --chips 4   # four chips: phase `zero4` only

Phase `train` runs the launcher's own loop (``repro.launch.train.train``)
on gpt2-paper-1b at its published widths, batch 8 x seq 1024, with every
optimizer-state group in pinned_host (``--os-host-fraction 1.0``), for 4
steps on one repeated batch.  Phase `kernels` compiles the Pallas fused
ADAM (one gpt2-paper-1b layer store) and flash attention ([1,2048,16,128]
bf16, causal) and compares each with ``repro.kernels.ref``.  Phase `zero4`
trains the same model and batch on a dp=4 mesh with the optimizer state on
the devices and compares its losses with the one-chip offloaded run on
chip 0.

Weights are random from seed 0.  Any failed check exits non-zero; on
success the last line of stdout is one JSON object naming the device.
The script refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# lr 1e-6: with no warmup, Adam's first steps move every weight by about
# lr in the sign of its gradient, and at d_model 2048 that overshoots
# sooner the deeper the model.  On one v5e the 20-layer loss on the
# repeated batch went 11.20 -> 17.91 -> 17.78 -> 13.15 at lr 1e-3 and
# 11.20 -> 17.64 -> 12.53 -> 12.14 at lr 1e-4; on the CPU a 6-layer cut
# rose at the fifth step at lr 1e-5 and fell steadily at 3e-6
TRAIN_ARGS = ["--arch", "gpt2-paper-1b", "--batch", "8", "--seq", "1024",
              "--steps", "4", "--repeat-batch", "--seed", "0", "--lr", "1e-6"]
# step 0 loss of a random-init LM: ln(50304) ~ 10.83, plus ~0.5 for the
# unit-variance logits the tied unembedding gives at init
FIRST_LOSS_RANGE = (10.0, 12.5)
# zero4 vs one chip, per-step loss: the same model, batch and optimizer,
# but dp=4 runs 2 sequences per chip and reduces the loss and the bf16
# gradients across chips in another order than one chip does.  At lr 1e-6
# a step moves each weight by about 1e-6, so the gap stays at the forward
# rounding seen at step 0, before any update (1.5e-5 on a 2x2 v5e).  A
# dp=4 state that never changed would read 8.8e-4 at step 1, 4.4e-3 at 3
ZERO4_LOSS_RTOL = 5e-4
# zero4 vs one chip, loss drop over the run: one chip's fell by 0.0495
# and dp=4's by 0.5% more.  A drop computed from other gradients (a rank
# updating its shard from its own sequences only) moves by far more
ZERO4_DROP_RTOL = 5e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def run_train(launcher, extra: list[str], tag: str):
    args = launcher.parser().parse_args(TRAIN_ARGS + extra)
    run = launcher.train(args, log=lambda s: print(f"[{tag}] {s}", flush=True))
    print(f"[{tag}] init_state_s={run.init_s:.3f} "
          f"train_step_compile_s={run.compile_s:.3f}")
    print(f"[{tag}] step_ms={run.step_ms}")
    print(f"[{tag}] losses={run.losses}")
    check(all(math.isfinite(x) for x in run.losses),
          f"{tag}: non-finite loss {run.losses}")
    return run


def phase_train(launcher, dev):
    run = run_train(launcher, ["--os-host-fraction", "1.0"], "train")
    stats = dev.memory_stats()
    print(f"[train] peak_bytes_in_use={stats['peak_bytes_in_use']}")
    print(f"[train] memory_stats={stats}")
    lo, hi = FIRST_LOSS_RANGE
    check(lo <= run.losses[0] <= hi,
          f"step-0 loss {run.losses[0]} outside [{lo}, {hi}]")
    check(run.losses[-1] < run.losses[0],
          f"loss did not fall on the repeated batch: {run.losses}")
    for name, st in run.osstores.items():
        for k, parts in st.items():
            for part, arr in parts.items():
                kind = arr.sharding.memory_kind
                print(f"[train] os store {name}/{k}/{part} "
                      f"shape={arr.shape} memory_kind={kind}")
                if part == "host":
                    check(arr.size > 0 and kind == "pinned_host",
                          f"{name}/{k}/host is {arr.shape} in {kind}")
    return run.rt.layouts["layers"].store_shape


def compiled(name, fn, *args):
    """Compile ``fn`` for ``args`` and check a Pallas kernel is in it."""
    import jax

    t0 = time.perf_counter()
    c = jax.jit(fn).lower(*args).compile()
    print(f"[kernels] {name} compile_s={time.perf_counter() - t0:.3f}")
    check("tpu_custom_call" in c.as_text(), "no Mosaic kernel in the program")
    return c


def phase_kernels(layer_store_shape):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    @jax.jit
    def within(got, want, rtol, atol):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = jnp.abs(got - want)
        return err.max(), jnp.all(err <= atol + rtol * jnp.abs(want))

    t0 = time.perf_counter()

    def report(name, got, want, rtol, atol):
        err, ok = within(got, want, rtol, atol)
        print(f"[kernels] {name} shape={got.shape} max_abs_err={float(err)} "
              f"rtol={rtol} atol={atol} at_s={time.perf_counter() - t0:.3f}")
        check(bool(ok), f"{name}: kernel differs from kernels/ref.py")

    # fused ADAM over the elements of one gpt2-paper-1b layer store, flat
    # as the kernel sees them: compiling for the store's [G, 1, S] shape
    # spends about two minutes relayouting it for the flat kernel.
    # Tolerance: the same fp32 elementwise ops in the same order; Mosaic
    # and XLA may differ in FMA contraction and in divide/sqrt by a few ulp
    k = jax.random.split(jax.random.key(1), 4)
    shape = (math.prod(layer_store_shape),)
    p32 = jax.random.normal(k[0], shape)
    m = jax.random.normal(k[1], shape) * 0.01
    v = jnp.abs(jax.random.normal(k[2], shape)) * 0.01
    g = jax.random.normal(k[3], shape).astype(jnp.bfloat16)
    bc = (jnp.float32(0.1), jnp.float32(0.05))
    hp = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1)

    def adam(p32, m, v, g, bc1, bc2):
        return ops.chunked_adam(p32, m, v, g, bias_corr1=bc1,
                                bias_corr2=bc2, **hp)

    got = compiled("adam", adam, p32, m, v, g, *bc)(p32, m, v, g, *bc)
    want = jax.jit(lambda *a: ref.adam_ref(*a[:4], bias_corr1=a[4],
                                           bias_corr2=a[5], **hp))(
        p32, m, v, g, *bc)
    for name, a, b in zip(("adam.p32", "adam.m", "adam.v"), got, want):
        report(name, a, b, rtol=1e-5, atol=1e-6)
    del p32, m, v, g, got, want

    # flash attention, causal, bf16 in/out, against the fp32 reference at
    # highest matmul precision.  Tolerance: bf16 output rounding (2^-8
    # relative) plus the kernel's bf16-operand MXU passes
    k = jax.random.split(jax.random.key(2), 3)
    q, kk, vv = (jax.random.normal(x, (1, 2048, 16, 128), jnp.bfloat16)
                 for x in k)
    flash = lambda q, k, v: ops.flash_attention(q, k, v, causal=True)
    got = compiled("flash_attention", flash, q, kk, vv)(q, kk, vv)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: ref.flash_attention_ref(*a, causal=True))(
            q, kk, vv)
    report("flash_attention", got, want, rtol=2e-2, atol=2e-2)


def phase_zero4(launcher):
    import jax

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 chips, "
          f"found {len(jax.devices())}")
    one = run_train(launcher, ["--os-host-fraction", "1.0"], "chip0")
    ref_losses = one.losses
    del one
    gc.collect()
    run = run_train(launcher, ["--dp", "4", "--os-host-fraction", "0.0"],
                    "zero4")
    # every store is split over the four chips along the ZeRO axis
    for name, arr in run.pstores.items():
        devs = {s.device.id for s in arr.addressable_shards}
        shard = arr.addressable_shards[0].data.shape
        print(f"[zero4] param store {name} global={arr.shape} "
              f"shard={shard} devices={sorted(devs)}")
        check(len(devs) == 4 and arr.shape[-2] == 4 * shard[-2],
              f"param store {name} is not split over 4 chips")
    for a, b in zip(run.losses, ref_losses):
        print(f"[zero4] loss dp4={a} chip0={b} rel={abs(a - b) / abs(b)}")
    check(len(run.losses) == len(ref_losses) and all(
        abs(a - b) <= ZERO4_LOSS_RTOL * abs(b)
        for a, b in zip(run.losses, ref_losses)),
        f"dp=4 losses {run.losses} differ from one chip's {ref_losses} "
        f"beyond rtol {ZERO4_LOSS_RTOL}")
    drop, ref_drop = (x[0] - x[-1] for x in (run.losses, ref_losses))
    print(f"[zero4] loss drop dp4={drop} chip0={ref_drop} "
          f"rel={abs(drop - ref_drop) / ref_drop}")
    check(ref_drop > 0 and drop > 0,
          f"loss did not fall on the repeated batch: dp4 {run.losses}, "
          f"chip0 {ref_losses}")
    check(abs(drop - ref_drop) <= ZERO4_DROP_RTOL * ref_drop,
          f"dp=4 loss drop {drop} differs from one chip's {ref_drop} "
          f"beyond rtol {ZERO4_DROP_RTOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=4 ZeRO phase and its comparison")
    chips = ap.parse_args().chips
    try:
        from repro.launch import train as launcher
    except ImportError as e:
        raise SystemExit(f"chip_smoke: run from a checkout of the repo ({e})")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    launcher.enable_compile_cache()
    t0 = time.perf_counter()
    if chips == 4:
        phase_zero4(launcher)
        print(f"[zero4] phase_s={time.perf_counter() - t0:.1f}")
    else:
        layer_store_shape = phase_train(launcher, dev)
        print(f"[train] phase_s={time.perf_counter() - t0:.1f}")
        gc.collect()
        t0 = time.perf_counter()
        phase_kernels(layer_store_shape)
        print(f"[kernels] phase_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": chips}}))


if __name__ == "__main__":
    main()
