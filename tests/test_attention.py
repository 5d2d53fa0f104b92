"""Attention correctness: scan==naive, SWA, GQA alignment, distributed
cache decode, MLA absorbed decode — each against a dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import BaseConfig, MoEConfig
from repro.models import layers as L
from repro.models import mla as MLA


def _qkv(key, b, sq, sk, h, kv, d, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (b, sq, h, d), dtype),
            jax.random.normal(k2, (b, sk, kv, d), dtype),
            jax.random.normal(k3, (b, sk, kv, d), dtype))


@pytest.mark.parametrize("sq,sk,h,kv,d,block", [
    (16, 16, 4, 4, 8, 8),
    (32, 32, 4, 2, 16, 16),
    (7, 23, 2, 1, 8, 8),   # ragged, GQA to 1 kv head
    (64, 64, 8, 8, 32, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_scan_matches_naive(sq, sk, h, kv, d, block, causal):
    q, k, v = _qkv(jax.random.key(0), 2, sq, sk, h, kv, d)
    if causal and sq != sk:
        pytest.skip("causal oracle assumes aligned q/k")
    want = L.naive_attention(q, k, v, causal=causal)
    got = L.scan_attention(q, k, v, causal=causal, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_masks():
    q, k, v = _qkv(jax.random.key(1), 1, 32, 32, 2, 2, 8)
    w_naive = L.naive_attention(q, k, v, causal=True, window=8)
    w_scan = L.scan_attention(q, k, v, causal=True, window=8, block=8)
    np.testing.assert_allclose(np.asarray(w_scan), np.asarray(w_naive),
                               rtol=2e-5, atol=2e-5)
    full = L.naive_attention(q, k, v, causal=True)
    assert not np.allclose(np.asarray(w_naive), np.asarray(full))


def _mesh(tp):
    from repro.launch.mesh import _mesh as mk

    return mk((1, tp), ("data", "model"))


@pytest.mark.parametrize("h,kv,tp", [(8, 2, 4), (8, 8, 4), (4, 2, 2)])
def test_tp_attention_matches_single_device(h, kv, tp):
    """fwd/prefill/decode under TP (incl. distributed-cache mode when
    kv % tp != 0) against the tp=1 full-attention oracle."""
    d, hd, B, S = 64, 16, 2, 12
    cfg = BaseConfig(name="t", d_model=d, n_heads=h, n_kv_heads=kv,
                     head_dim=hd, d_ff=64, vocab_size=64)
    ctx = L.AxisCtx(model_axis="model", tp=tp, data_axis="data", dp=1)
    key = jax.random.key(0)
    kq, kk, kv_, ko, kx = jax.random.split(key, 5)
    wq = L.dense_init(kq, (d, h * hd))
    wk = L.dense_init(kk, (d, kv * hd))
    wv = L.dense_init(kv_, (d, kv * hd))
    wo = L.dense_init(ko, (h * hd, d))
    x = jax.random.normal(kx, (B, S, d))
    p1 = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    ref = L.attention_fwd(p1, x, cfg, L.AxisCtx())

    kv_sharded = kv % tp == 0

    def run(x):
        rank = jax.lax.axis_index("model")
        h_l = h // tp
        p = {"wq": jax.lax.dynamic_slice_in_dim(wq, rank * h_l * hd, h_l * hd, 1),
             "wo": jax.lax.dynamic_slice_in_dim(wo, rank * h_l * hd, h_l * hd, 0)}
        if kv_sharded:
            kv_l = kv // tp
            p["wk"] = jax.lax.dynamic_slice_in_dim(wk, rank * kv_l * hd, kv_l * hd, 1)
            p["wv"] = jax.lax.dynamic_slice_in_dim(wv, rank * kv_l * hd, kv_l * hd, 1)
        else:
            p["wk"], p["wv"] = wk, wv
        y_fwd = L.attention_fwd(p, x, cfg, ctx)
        y_pre, cache = L.attention_prefill(p, x, cfg, ctx)
        cache2 = L.attention_init_cache(cfg, B, S, tp, jnp.float32)
        y_dec = x[:, :1] * 0
        for i in range(S):
            y_dec, cache2 = L.attention_decode(p, x[:, i:i + 1], cache2, i, cfg, ctx)
        return y_fwd, y_pre, y_dec

    f = jax.jit(jax.shard_map(run, mesh=_mesh(tp), in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    y_fwd, y_pre, y_dec = f(x)
    np.testing.assert_allclose(np.asarray(y_fwd), np.asarray(ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]), np.asarray(ref[:, -1]),
                               atol=2e-4)


def test_prefill_then_decode_continues():
    """decode continuing from a prefilled distributed cache (kv % tp != 0)."""
    d, h, kv, hd, tp, B, S = 64, 8, 2, 16, 4, 2, 8
    cfg = BaseConfig(name="t", d_model=d, n_heads=h, n_kv_heads=kv,
                     head_dim=hd, d_ff=64, vocab_size=64)
    ctx = L.AxisCtx(model_axis="model", tp=tp, data_axis="data", dp=1)
    key = jax.random.key(3)
    kq, kk, kv_, ko, kx = jax.random.split(key, 5)
    wq = L.dense_init(kq, (d, h * hd)); wk = L.dense_init(kk, (d, kv * hd))
    wv = L.dense_init(kv_, (d, kv * hd)); wo = L.dense_init(ko, (h * hd, d))
    x = jax.random.normal(kx, (B, S + 2, d))
    ref = L.attention_fwd({"wq": wq, "wk": wk, "wv": wv, "wo": wo},
                          x, cfg, L.AxisCtx())

    def run(x):
        rank = jax.lax.axis_index("model")
        h_l = h // tp
        p = {"wq": jax.lax.dynamic_slice_in_dim(wq, rank * h_l * hd, h_l * hd, 1),
             "wk": wk, "wv": wv,
             "wo": jax.lax.dynamic_slice_in_dim(wo, rank * h_l * hd, h_l * hd, 0)}
        _, cache = L.attention_prefill(p, x[:, :S], cfg, ctx)
        # grow the prefill cache chunks to the decode horizon
        full = L.attention_init_cache(cfg, B, S + 2, tp, cache["k"].dtype)
        cache = {k2: jax.lax.dynamic_update_slice(
            full[k2], cache[k2], (0, 0, 0, 0)) for k2 in cache}
        y = None
        for i in range(2):
            y, cache = L.attention_decode(p, x[:, S + i:S + i + 1], cache,
                                          S + i, cfg, ctx)
        return y

    f = jax.jit(jax.shard_map(run, mesh=_mesh(tp), in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    y = f(x)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(ref[:, -1]),
                               atol=2e-4)


@pytest.mark.parametrize("tp", [1, 4])
def test_mla_decode_matches_fwd(tp):
    cfg = MoEConfig(name="mla-t", d_model=64, n_heads=4, n_kv_heads=4,
                    head_dim=32, d_ff=64, d_ff_expert=32, vocab_size=64,
                    kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, n_experts=4, top_k=2)
    B, S = 2, 10
    ctx1 = L.AxisCtx()
    p1 = MLA.init_mla(jax.random.key(0), cfg, 1, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model))
    ref = MLA.mla_fwd(p1, x, cfg, ctx1)

    if tp == 1:
        cache = MLA.mla_init_cache(cfg, B, S, jnp.float32, tp=1)
        y = None
        for i in range(S):
            y, cache = MLA.mla_decode(p1, x[:, i:i + 1], cache, i, cfg, ctx1)
        np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(ref[:, -1]),
                                   atol=2e-4)
        return

    ctx = L.AxisCtx(model_axis="model", tp=tp, data_axis="data", dp=1)
    h_l = cfg.n_heads // tp
    nr = cfg.qk_nope_dim + cfg.qk_rope_dim

    def run(x):
        rank = jax.lax.axis_index("model")
        def sl(w, width):
            return jax.lax.dynamic_slice_in_dim(w, rank * h_l * width,
                                                h_l * width, 1)
        p = {"wq": sl(p1["wq"], nr), "w_dkv": p1["w_dkv"],
             "w_krope": p1["w_krope"], "kv_norm": p1["kv_norm"],
             "w_uk": sl(p1["w_uk"], cfg.qk_nope_dim),
             "w_uv": sl(p1["w_uv"], cfg.v_head_dim),
             "wo": jax.lax.dynamic_slice_in_dim(
                 p1["wo"], rank * h_l * cfg.v_head_dim,
                 h_l * cfg.v_head_dim, 0)}
        cache = MLA.mla_init_cache(cfg, B, S, jnp.float32, tp=tp)
        y = None
        for i in range(S):
            y, cache = MLA.mla_decode(p, x[:, i:i + 1], cache, i, cfg, ctx)
        return y

    f = jax.jit(jax.shard_map(run, mesh=_mesh(tp), in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    y = f(x)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(ref[:, -1]),
                               atol=2e-4)
