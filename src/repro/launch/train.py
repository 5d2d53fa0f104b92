"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gpt2-paper-1b \\
      --os-host-fraction 1.0 --batch 8 --seq 1024 --steps 20

Runs the chunked ZeRO runtime end-to-end on the devices present, with the
synthetic data pipeline, checkpointing, and metrics logging.  The mesh
takes ``--pods x --dp x --tp`` devices; under ``JAX_PLATFORMS=cpu`` the
launcher fakes that many host devices (``--devices N`` overrides the
count), while on an accelerator a mesh larger than the chips present is
an error.  :func:`train` is the loop itself, shared by the CLI and
``chip_smoke.py``.  ``--profile DIR`` writes a ``jax.profiler`` trace of
steps 2 to 4 under DIR: each step is a ``train`` step span, its batch
preparation and transfer a ``feed`` span, beside the device's ops, whose
names carry the train step's named scopes (``runtime/step.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Any, Callable

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(REPO_ROOT / ".jax_cache"))


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="gpt2-paper-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="host devices to fake under JAX_PLATFORMS=cpu "
                         "(default: the mesh size)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--repeat-batch", action="store_true",
                    help="train on the first batch every step (overfit check)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--gather-policy", default="layer", choices=["layer", "step"])
    ap.add_argument("--os-host-fraction", type=float, default=0.0)
    ap.add_argument("--chunk-size", type=int, default=None)
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler trace of steps 2 to 4 under DIR")
    return ap


@dataclasses.dataclass
class TrainRun:
    """What one :func:`train` call leaves behind."""

    rt: Any  # ChunkedRuntime
    pstores: Any
    osstores: Any
    losses: list[float]  # one per logged step
    step_ms: list[float]  # host wall time of each logged step, synced
    init_s: float  # init_state: compile + run
    compile_s: float  # train step compile


def train(args: argparse.Namespace, *,
          log: Callable[[str], None] = print) -> TrainRun:
    """Build the runtime from ``args`` (see :func:`parser`), initialise
    the stores, compile the step and run ``args.steps`` steps."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import checkpoint as ckpt
    from repro.configs import get_config, model_class
    from repro.configs.base import InputShape
    from repro.data.pipeline import make_batch_fn
    from repro.launch.mesh import make_smoke_mesh
    from repro.runtime import driver
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.param_dtype:
        cfg = cfg.replace(param_dtype=args.param_dtype,
                          compute_dtype=args.param_dtype)
    mesh = make_smoke_mesh(args.dp, args.tp, args.pods)
    options = RuntimeOptions(
        remat=args.remat, gather_policy=args.gather_policy,
        os_host_fraction=args.os_host_fraction, chunk_size=args.chunk_size,
        lr=args.lr)
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh, options)
    n_params = sum(
        int(jnp.prod(jnp.array(s.shape)))
        for s in jax.tree.leaves(rt.model.param_specs()))
    log(f"arch={cfg.name} mesh={dict(mesh.shape)} "
        f"tp-local params={n_params/1e6:.1f}M "
        f"layouts={[(k, v.store_shape, round(v.cmap.utilization, 3)) for k, v in rt.layouts.items()]}")

    t0 = time.perf_counter()
    pstores, osstores = jax.block_until_ready(
        driver.init_state(rt, jax.random.key(args.seed)))
    init_s = time.perf_counter() - t0
    shape = InputShape("cli", args.seq, args.batch, "train")
    step_fn, arg_specs, in_shardings = driver.build_train_step(rt, shape)
    t0 = time.perf_counter()
    step_fn = step_fn.lower(*arg_specs).compile()
    compile_s = time.perf_counter() - t0
    log(f"init_state {init_s:.1f} s  train-step compile {compile_s:.1f} s  "
        f"slices fetched ahead {rt.pipelined_slices()}")
    next_batch = make_batch_fn(cfg, args.batch, args.seq, seed=args.seed)

    if args.profile and args.steps < 3:
        raise ValueError("--profile traces steps 2 to 4: give --steps 3 or more")
    losses, step_ms = [], []
    batch = None
    profiled = range(2, min(5, args.steps)) if args.profile else range(0)
    for step in range(args.steps):
        if profiled and step == profiled[0]:
            jax.profiler.start_trace(args.profile)
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            with jax.profiler.TraceAnnotation("feed"):
                if batch is None or not args.repeat_batch:
                    batch = jax.device_put(
                        {k: v for k, v in next_batch().items() if k != "mask"},
                        in_shardings[2])
            pstores, osstores, metrics = step_fn(
                pstores, osstores, batch, jnp.int32(step))
            if step % args.log_every == 0:
                loss = float(metrics["loss"])  # waits for the step
                dt = time.perf_counter() - t0
                losses.append(loss)
                step_ms.append(dt * 1e3)
                log(f"step {step:4d}  loss {loss:.4f}  "
                    f"aux {float(metrics['aux_loss']):.4f}  {dt*1e3:.0f} ms")
        if profiled and step == profiled[-1]:
            jax.block_until_ready(metrics)
            jax.profiler.stop_trace()
            log(f"profile of steps {profiled[0]} to {step} written under {args.profile}")
        if (args.checkpoint and args.checkpoint_every
                and (step + 1) % args.checkpoint_every == 0):
            ckpt.save(rt, pstores, osstores, args.checkpoint, step=step + 1)
    if args.checkpoint:
        ckpt.save(rt, pstores, osstores, args.checkpoint, step=args.steps)
        log(f"saved checkpoint to {args.checkpoint}")
    return TrainRun(rt, pstores, osstores, losses, step_ms, init_s, compile_s)


def main(argv: list[str] | None = None) -> None:
    from repro.launch.mesh import fake_cpu_devices

    args = parser().parse_args(argv)
    fake_cpu_devices(args.devices or (args.pods * args.dp * args.tp))
    enable_compile_cache()
    train(args)


if __name__ == "__main__":
    main()
