"""End-to-end driver: chunked-ZeRO training of a GPT on the compiled
runtime (deliverable b): synthetic data pipeline -> shard_map train step
-> chunked Adam -> checkpoint.

Default is a CPU-sized run; the full assignment-scale command is

    PYTHONPATH=src python examples/train_gpt_hetero.py \
        --layers 12 --d-model 768 --steps 300 --batch 8 --seq 512 \
        --dp 2 --tp 2            # ~100M params, a few hundred steps

(``--dp``/``--tp`` above 1 need that many chips; under
``JAX_PLATFORMS=cpu`` the example fakes them.)
"""

import argparse
import pathlib
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--checkpoint", default=str(
        pathlib.Path(__file__).resolve().parents[1] / "checkpoints" / "gpt"))
    args = ap.parse_args()

    from repro.launch.mesh import fake_cpu_devices

    fake_cpu_devices(args.dp * args.tp)

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import checkpoint as ckpt
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import make_batch_fn
    from repro.launch.mesh import make_smoke_mesh
    from repro.models.transformer import TransformerLM
    from repro.runtime import driver
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    heads = max(args.d_model // 64, 4)
    cfg = get_config("gpt2-paper-1b").replace(
        name="gpt-example", num_layers=args.layers, d_model=args.d_model,
        n_heads=heads, n_kv_heads=heads, head_dim=64, d_ff=4 * args.d_model,
        vocab_size=50304)
    mesh = make_smoke_mesh(args.dp, args.tp)
    rt = ChunkedRuntime(TransformerLM, cfg, mesh,
                        RuntimeOptions(lr=3e-4, xent_block=1024))
    n = sum(int(jnp.prod(jnp.asarray(s.shape)))
            for s in jax.tree.leaves(rt.model.param_specs())) * args.tp
    print(f"params ~{n/1e6:.1f}M  mesh={dict(mesh.shape)}  "
          f"chunk layouts: "
          f"{[(k, v.store_shape) for k, v in rt.layouts.items()]}")

    shape = InputShape("train", args.seq, args.batch, "train")
    step_fn, _, _ = driver.build_train_step(rt, shape)
    ps, oss = driver.init_state(rt, jax.random.key(0))
    next_batch = make_batch_fn(cfg, args.batch, args.seq)
    t0 = time.perf_counter()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next_batch().items()
                 if k != "mask"}
        ps, oss, m = step_fn(ps, oss, batch, jnp.int32(step))
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"({(time.perf_counter()-t0)/(step+1)*1e3:.0f} ms/step avg)")
    ckpt.save(rt, ps, oss, args.checkpoint, step=args.steps)
    print("checkpoint saved to", args.checkpoint)


if __name__ == "__main__":
    main()
