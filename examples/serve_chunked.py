"""Serving on the chunked runtime, both planes:

1. **Compiled**: prefill a prompt batch, then greedy-decode continuation
   tokens, with params living in ZeRO chunk stores gathered per layer
   (weight-offloaded inference).
2. **Chunk-managed (eager)**: the same decoding through
   :class:`~repro.core.serving.ServingEngine`, where the KV caches are a
   managed chunk stream in the heterogeneous pool — requests arrive
   staggered, queue when the budget is full, spill cold KV to host, and
   free their chunks the moment they complete (continuous batching).
"""

from repro.launch.mesh import fake_cpu_devices

fake_cpu_devices(4)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, model_class
from repro.configs.base import InputShape
from repro.core.serving import ServingEngine
from repro.launch.mesh import make_smoke_mesh
from repro.runtime import driver
from repro.runtime.step import ChunkedRuntime, RuntimeOptions


def compiled_demo(cfg):
    # up to dp=2 x tp=2, as far as the devices present allow
    tp = min(2, len(jax.devices()))
    mesh = make_smoke_mesh(min(2, len(jax.devices()) // tp), tp)
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh, RuntimeOptions())
    ps, _ = driver.init_state(rt, jax.random.key(0))

    B, S, new_tokens = 4, 16, 8
    horizon = S + new_tokens
    prompts = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)

    # decode path sized to the horizon; replay the prompt then continue
    shape = InputShape("serve", horizon, B, "decode")
    dec, _ = driver.build_decode_step(rt, shape)
    caches = driver.init_caches(rt, shape)
    tok = prompts[:, :1]
    seqs = [np.asarray(prompts)]
    for i in range(horizon - 1):
        nxt, caches = dec(ps, caches,
                          prompts[:, i:i + 1] if i < S else tok,
                          jnp.int32(i))
        if i >= S - 1:
            tok = nxt[:, None].astype(jnp.int32)
            seqs.append(np.asarray(tok))
    out = np.concatenate(seqs, axis=1)
    print("compiled prompt + continuation token ids:")
    for row in out:
        print(" ", row.tolist())
    assert out.shape == (B, S + new_tokens)


def chunk_managed_demo(cfg):
    horizon, new_tokens = 40, 8
    eng = ServingEngine(model_class(cfg), cfg,
                        device_memory_bytes=1_200_000,  # < param stream!
                        host_memory_bytes=8_000_000,
                        max_seq_len=horizon, seed=0)
    print(f"\nchunk-managed serving: device budget "
          f"{eng.device_capacity/1e6:.1f}MB vs param stream "
          f"{eng._param_stream_bytes/1e6:.1f}MB "
          f"+ {eng.kv_seq_bytes/1e3:.0f}KB KV per sequence")
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (6, 12), 0, cfg.vocab_size))
    # staggered arrivals: two requests join mid-flight (continuous
    # batching admits them while earlier sequences keep decoding)
    rids = [eng.submit(p, new_tokens) for p in prompts[:4]]
    for _ in range(2):
        m = eng.step_round()
        print(f"  round {m.round_index}: active={m.active} "
              f"queued={m.queued} tokens={m.tokens} "
              f"spill d2h={m.d2h_bytes/1e3:.0f}KB "
              f"prefetch hits={m.prefetch_hits}")
    rids += [eng.submit(p, new_tokens) for p in prompts[4:]]
    for m in eng.run():
        print(f"  round {m.round_index}: active={m.active} "
              f"queued={m.queued} tokens={m.tokens} "
              f"spill d2h={m.d2h_bytes/1e3:.0f}KB "
              f"prefetch hits={m.prefetch_hits}")
    print("generated token ids:")
    for rid in rids:
        print(f"  req {rid}: {eng.result(rid)}")
    eng.check_invariants()
    st = eng.pool.stats
    print(f"pool: h2d {st.h2d_bytes/1e6:.1f}MB, d2h {st.d2h_bytes/1e6:.1f}MB, "
          f"peak device {eng.pool.peak_device_bytes/1e6:.2f}MB "
          f"(budget {eng.device_capacity/1e6:.1f}MB), "
          f"prefetch hit-rate {eng.pool.prefetch.hit_rate:.0%}")


def main():
    cfg = get_config("qwen3-0.6b", smoke=True)
    compiled_demo(cfg)
    chunk_managed_demo(cfg.replace(param_dtype="float32",
                                   compute_dtype="float32"))


if __name__ == "__main__":
    main()
