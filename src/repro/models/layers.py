"""Common model layers, written for shard_map SPMD execution.

Conventions
-----------
* All functions are pure; params are plain dicts of jax arrays.
* Tensor-parallel (TP) sharding is *explicit*: param shapes passed in are
  the TP-LOCAL shards, and layers perform the required ``psum`` over the
  model axis themselves, driven by :class:`AxisCtx`.  With ``tp == 1`` the
  ctx degenerates and no collectives are emitted.
* Attention/MLP follow the Megatron pattern: column-parallel in
  (q/k/v, up/gate), row-parallel out (o, down) with one psum per block.
* Activations stay in the compute dtype (bf16 by default); matmuls
  accumulate in fp32 via ``preferred_element_type``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class AxisCtx:
    """Mesh axis context available inside shard_map."""

    model_axis: str | None = None
    tp: int = 1
    data_axis: str | None = None
    dp: int = 1
    pod_axis: str | None = None
    pods: int = 1
    # implementation switches
    attn_impl: str = "auto"  # "naive" | "scan" | "auto"
    attn_block: int = 512  # kv block for the scan/flash impl
    # §Perf: checkpoint inner sequence scans (SSD/mLSTM/sLSTM/flash-scan
    # bodies) so their backward recomputes per-step intermediates instead
    # of storing them — the "memory term" hillclimb
    inner_remat: bool = False
    # §Perf: MoE combines expert outputs BEFORE the model-axis psum
    # ([T,d] instead of [E,C,d] payload) — the "collective term" hillclimb
    moe_combine_first: bool = False
    # §Perf: compute the vocab-parallel cross-entropy blockwise over the
    # sequence (fp32 logits live range / n_blocks) — 0 disables
    xent_block: int = 0

    # NOTE: collectives are gated on axis PRESENCE, not axis size — with
    # shard_map's check_vma=True, a psum over a size-1 mesh axis is a
    # typing no-op that marks the value invariant over that axis (and the
    # transpose machinery needs it for correct gradients).
    def psum_model(self, x):
        return jax.lax.psum(x, self.model_axis) if self.model_axis else x

    def pmax_model(self, x):
        return jax.lax.pmax(x, self.model_axis) if self.model_axis else x

    def model_rank(self):
        if self.model_axis:
            return jax.lax.axis_index(self.model_axis)
        return jnp.int32(0)

    def dp_axes(self) -> tuple[str, ...]:
        axes = []
        if self.pod_axis:
            axes.append(self.pod_axis)
        if self.data_axis:
            axes.append(self.data_axis)
        return tuple(axes)


# ---------------------------------------------------------------------------
# varying-manual-axes (vma) helpers for shard_map's check_vma=True
# ---------------------------------------------------------------------------


def all_axes(ctx: "AxisCtx") -> tuple[str, ...]:
    return tuple(a for a in (ctx.pod_axis, ctx.data_axis, ctx.model_axis) if a)


def vary_to(x, axes: tuple[str, ...]):
    """pcast ``x`` to varying over ``axes`` (idempotent, typing-only)."""
    if not axes or not hasattr(x, "dtype"):
        return x
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in vma)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def vary_tree(tree, axes: tuple[str, ...]):
    """Stabilize a scan carry's vma type: cast every leaf to varying over
    ``axes``.  Values are unchanged; the pcast transpose (psum over the
    added axes) is exactly the correct gradient rule for an invariant
    value consumed by device-varying computation."""
    if not axes:
        return tree
    return jax.tree.map(lambda t: vary_to(t, axes), tree)


# ---------------------------------------------------------------------------
# initializers / numerics helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def matmul(x, w, ctx_dtype=None):
    out = jnp.einsum("...d,df->...f", x, w, preferred_element_type=jnp.float32)
    return out.astype(ctx_dtype or x.dtype)


def rms_norm(x, weight, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def squared_relu(x):
    r = jnp.maximum(x, 0)
    return r * r


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": partial(jax.nn.gelu, approximate=True),
    "relu2": squared_relu,
    "relu": jax.nn.relu,
}


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # [head_dim//2]


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., seq, heads, head_dim]; positions: [..., seq] (int)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention cores (pure math on [B, S, H, Dh] tensors)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def naive_attention(q, k, v, *, causal: bool, window: int | None = None,
                    q_offset: int | jax.Array = 0, kv_len: jax.Array | None = None,
                    scale: float | None = None):
    """Reference attention. q: [B,Sq,H,D], k/v: [B,Sk,KV,D] (KV divides H)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    qpos = jnp.arange(sq) + q_offset  # [Sq]
    kpos = jnp.arange(sk)  # [Sk]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32).astype(q.dtype)


def scan_attention(q, k, v, *, causal: bool, window: int | None = None,
                   q_offset: int | jax.Array = 0, kv_len: jax.Array | None = None,
                   scale: float | None = None, block: int = 512,
                   vary_axes: tuple = (), inner_remat: bool = False):
    """Online-softmax (flash-style) attention as a jnp scan over KV blocks.

    Linear memory in KV length — this is what the big dry-run shapes lower
    (the Pallas flash kernel implements the same schedule for real TPUs;
    ``kernels/flash_attention/ref.py`` cross-checks both against
    :func:`naive_attention`).
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # may differ from the qk dim (e.g. MLA)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    rep = h // kvh
    nblk = -(-sk // block)
    pad = nblk * block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block, kvh, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block, kvh, dv).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(sq) + q_offset
    q32 = q.astype(jnp.float32) * scale

    def body(carry, inp):
        acc, m, l = carry
        blk_idx, kblk, vblk = inp  # kblk: [B, block, KV, D]
        if rep != 1:
            kblk = jnp.repeat(kblk, rep, axis=2)
            vblk = jnp.repeat(vblk, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, kblk.astype(jnp.float32))
        kpos = blk_idx * block + jnp.arange(block)
        mask = jnp.ones((sq, block), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        mask &= kpos[None, :] < (sk if kv_len is None else kv_len)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    carry0 = vary_tree((acc0, m0, l0), vary_axes)
    vbody = lambda c, i: (vary_tree(body(c, i)[0], vary_axes), None)
    if inner_remat:
        vbody = jax.checkpoint(vbody)
    (acc, m, l), _ = jax.lax.scan(
        vbody, carry0, (jnp.arange(nblk), kb, vb)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_core(q, k, v, ctx: AxisCtx, **kw):
    impl = ctx.attn_impl
    if impl == "auto":
        impl = "scan" if (k.shape[1] > 2048 or q.shape[1] > 2048) else "naive"
    if impl == "scan":
        return scan_attention(q, k, v, block=ctx.attn_block,
                              vary_axes=all_axes(ctx),
                              inner_remat=ctx.inner_remat, **kw)
    return naive_attention(q, k, v, **kw)


# ---------------------------------------------------------------------------
# GQA attention block (column/row parallel over the model axis)
# ---------------------------------------------------------------------------


def gqa_shapes(d_model: int, n_heads: int, n_kv: int, head_dim: int, tp: int):
    """TP-local head counts.

    Query heads divide over tp; KV heads divide when possible, otherwise
    are replicated (GQA with few KV heads).  When even the query heads do
    not divide (e.g. whisper's 20 heads on a 16-way model axis) the whole
    attention block is replicated across the model axis — correct, at the
    cost of redundant attention compute; the MLP still TP-shards.  The
    third return value says whether attention is replicated (no out-psum,
    all params TP-axis None).
    """
    if n_heads % tp != 0:
        return n_heads, n_kv, True
    h_local = n_heads // tp
    kv_local = n_kv // tp if n_kv % tp == 0 else n_kv
    return h_local, kv_local, False


def init_attention(key, cfg, tp: int, dtype=jnp.float32) -> dict:
    """cfg needs: d_model, n_heads, n_kv_heads, head_dim, qk_norm, qkv_bias."""
    d, hd = cfg.d_model, cfg.head_dim
    h_l, kv_l, _ = gqa_shapes(d, cfg.n_heads, cfg.n_kv_heads, hd, tp)
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, h_l * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, kv_l * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, kv_l * hd), dtype=dtype),
        "wo": dense_init(ks[3], (h_l * hd, d), dtype=dtype),
    }
    if getattr(cfg, "qkv_bias", False):
        p["bq"] = jnp.zeros((h_l * hd,), dtype)
        p["bk"] = jnp.zeros((kv_l * hd,), dtype)
        p["bv"] = jnp.zeros((kv_l * hd,), dtype)
    if getattr(cfg, "qk_norm", False):
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_tp_axes(cfg, tp: int = 1) -> dict:
    """Which axis of each param is TP-sharded (None = replicated)."""
    _, _, replicated = gqa_shapes(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, tp)
    kv_repl = replicated or (tp > 1 and cfg.n_kv_heads % tp != 0)
    if replicated:
        axes = {"wq": None, "wk": None, "wv": None, "wo": None}
    else:
        axes = {"wq": 1, "wk": None if kv_repl else 1,
                "wv": None if kv_repl else 1, "wo": 0}
    if getattr(cfg, "qkv_bias", False):
        axes.update({"bq": None if replicated else 0,
                     "bk": None if kv_repl else 0,
                     "bv": None if kv_repl else 0})
    if getattr(cfg, "qk_norm", False):
        axes.update({"q_norm": None, "k_norm": None})
    return axes


def _project_qkv(p, x, cfg, ctx: AxisCtx, positions):
    b, s, d = x.shape
    hd = cfg.head_dim
    h_l, kv_l, _ = gqa_shapes(d, cfg.n_heads, cfg.n_kv_heads, hd, ctx.tp)
    q = matmul(x, p["wq"])
    k = matmul(x, p["wk"])
    v = matmul(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h_l, hd)
    k = k.reshape(b, s, kv_l, hd)
    v = v.reshape(b, s, kv_l, hd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    theta = getattr(cfg, "rope_theta", 10000.0)
    if getattr(cfg, "use_rope", True):
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v



def _align_kv(k, v, cfg, ctx: AxisCtx):
    """Select the kv heads matching this rank's local q-head slice.

    When kv heads are replicated (kv %% tp != 0) but q heads are sharded,
    the naive GQA repeat pairs local q head i with kv head i — wrong.
    Pick kv head (global_q_idx * KV) // H per local q head instead."""
    H, KV = cfg.n_heads, cfg.n_kv_heads
    h_l, kv_l, replicated = gqa_shapes(cfg.d_model, H, KV, cfg.head_dim, ctx.tp)
    if ctx.tp <= 1 or replicated or KV % ctx.tp == 0:
        return k, v
    rank = ctx.model_rank()
    qidx = rank * h_l + jnp.arange(h_l)
    kvidx = (qidx * KV) // H
    return jnp.take(k, kvidx, axis=2), jnp.take(v, kvidx, axis=2)

def attention_fwd(p, x, cfg, ctx: AxisCtx, *, positions=None, causal=True):
    """Full-sequence attention (training / prefill). x: [B, S, d]."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(p, x, cfg, ctx, positions)
    ka, va = _align_kv(k, v, cfg, ctx)
    window = getattr(cfg, "sliding_window", None)
    out = attention_core(q, ka, va, ctx, causal=causal, window=window)
    out = out.reshape(b, s, -1)
    y = matmul(out, p["wo"], jnp.float32)
    if not gqa_shapes(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd_r := cfg.head_dim, ctx.tp)[2]:
        y = ctx.psum_model(y)
    return y.astype(x.dtype)


def attention_prefill(p, x, cfg, ctx: AxisCtx, *, positions=None):
    """Prefill returning output and the KV cache."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(p, x, cfg, ctx, positions)
    ka, va = _align_kv(k, v, cfg, ctx)
    window = getattr(cfg, "sliding_window", None)
    out = attention_core(q, ka, va, ctx, causal=True, window=window)
    out = out.reshape(b, s, -1)
    y = matmul(out, p["wo"], jnp.float32)
    if not gqa_shapes(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, ctx.tp)[2]:
        y = ctx.psum_model(y)
    cache = _prefill_cache(k, v, s, cfg, ctx)
    return y.astype(x.dtype), cache


def _prefill_cache(k, v, s, cfg, ctx: AxisCtx):
    """Slice the freshly computed K/V into this rank's cache layout."""
    mode, kv_l, seq_shards = decode_cache_plan(cfg, ctx.tp)
    window = getattr(cfg, "sliding_window", None)
    if mode == "tp":
        if window and s > window:
            k = k[:, -window:]
            v = v[:, -window:]
        return {"k": k, "v": v}
    # distributed layout: pad seq (or window ring) to seq_shards chunks,
    # keep my (kv group, seq chunk)
    rank = ctx.model_rank()
    kv_grp = rank // seq_shards
    seq_idx = rank % seq_shards
    # wk/wv are replicated in dist mode -> k holds all KV heads
    k_my = jax.lax.dynamic_slice_in_dim(k, kv_grp * kv_l, kv_l, axis=2)
    v_my = jax.lax.dynamic_slice_in_dim(v, kv_grp * kv_l, kv_l, axis=2)
    ring = min(s, window) if window else s
    c_l = -(-ring // seq_shards)
    pad = c_l * seq_shards - ring
    if window and s > window:
        # keep the last `ring` positions, laid out at slot = pos % ring:
        # cache[i] holds position from last_ring[(i - s) mod ring]
        k_my = k_my[:, -ring:]
        v_my = v_my[:, -ring:]
        perm = jnp.mod(jnp.arange(ring) - s, ring)
        k_my = jnp.take(k_my, perm, axis=1)
        v_my = jnp.take(v_my, perm, axis=1)
    if pad:
        k_my = jnp.pad(k_my, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_my = jnp.pad(v_my, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # strided slot ownership: rank keeps slots seq_idx, seq_idx+shards, ...
    idx = jnp.arange(c_l) * seq_shards + seq_idx
    k_chunk = jnp.take(k_my, idx, axis=1)
    v_chunk = jnp.take(v_my, idx, axis=1)
    return {"k": k_chunk, "v": v_chunk}


def decode_cache_plan(cfg, tp: int):
    """How the decode KV cache distributes over the model axis.

    Returns (mode, kv_local, seq_shards):
      mode "tp":   kv heads divide tp — each rank stores kv/tp heads, full
                   sequence (classic TP cache).
      mode "dist": kv heads do NOT divide tp.  Replicating the cache
                   across the model axis would cost tp x the ideal HBM
                   (e.g. 77 GB/chip for qwen2.5-3b @ decode_32k), so the
                   cache is sharded over (kv-head groups x sequence
                   chunks): g = gcd(kv, tp) head groups, tp/g sequence
                   chunks; rank r holds kv/g heads of group r // (tp/g)
                   and sequence chunk r % (tp/g).  Decode combines the
                   per-rank partial attention with an exp-weighted psum
                   (distributed online softmax).
    """
    kv = cfg.n_kv_heads
    if tp <= 1 or kv % tp == 0:
        return "tp", max(kv // max(tp, 1), 1) if tp > 1 else kv, 1
    g = math.gcd(kv, tp)
    return "dist", kv // g, tp // g


def attention_init_cache(cfg, batch: int, max_len: int, tp: int, dtype) -> dict:
    window = getattr(cfg, "sliding_window", None)
    cache_len = min(max_len, window) if window else max_len
    mode, kv_l, seq_shards = decode_cache_plan(cfg, tp)
    if mode == "tp":
        _, kv_l, _ = gqa_shapes(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.head_dim, tp)
    cache_len = -(-cache_len // seq_shards)  # per-rank seq chunk
    z = jnp.zeros((batch, cache_len, kv_l, cfg.head_dim), dtype)
    return {"k": z, "v": z}


def attention_decode(p, x, cache, pos, cfg, ctx: AxisCtx):
    """Single-token decode. x: [B, 1, d]; pos: scalar int (current index);
    cache k/v: [B, C, KV_l, hd] (C covers the window for SWA, else the max
    length; divided by seq_shards in distributed-cache mode)."""
    mode, kv_l, seq_shards = decode_cache_plan(cfg, ctx.tp)
    if mode == "dist":
        return _attention_decode_dist(p, x, cache, pos, cfg, ctx,
                                      kv_l, seq_shards)
    b = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos)[None], (b, 1))
    q, k, v = _project_qkv(p, x, cfg, ctx, positions)
    window = getattr(cfg, "sliding_window", None)
    cache_len = cache["k"].shape[1]
    slot = jnp.mod(pos, cache_len) if window else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    if window:
        # ring buffer: positions of slots = pos - ((slot - j) mod C)
        j = jnp.arange(cache_len)
        slot_pos = pos - jnp.mod(slot - j, cache_len)
        valid = (slot_pos >= 0) & (slot_pos > pos - window)
        out = _decode_attend(q, ck, cv, valid)
    else:
        kv_len = pos + 1
        out = _decode_attend(q, ck, cv, jnp.arange(cache_len) < kv_len)
    out = out.reshape(b, 1, -1)
    y = matmul(out, p["wo"], jnp.float32)
    if not gqa_shapes(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, ctx.tp)[2]:
        y = ctx.psum_model(y)
    return y.astype(x.dtype), {"k": ck, "v": cv}


def _dist_slot_validity(pos, cache_len_local, seq_idx, window, seq_shards):
    """Global slot positions for this rank's cache chunk + validity mask.

    Slot ownership is STRIDED (round-robin): global slot s lives on rank
    s % seq_shards at local index s // seq_shards — so a prefill cache can
    grow to a longer decode horizon by appending local slots, with no
    cross-rank reshuffle.  For SWA the global slot array is a ring over
    the window."""
    j = jnp.arange(cache_len_local)
    gslot = j * seq_shards + seq_idx
    if window:
        ring = seq_shards * cache_len_local
        cur = jnp.mod(pos, ring)
        slot_pos = pos - jnp.mod(cur - gslot, ring)
        valid = (slot_pos >= 0) & (slot_pos > pos - window)
    else:
        valid = gslot <= pos
    return gslot, valid


def _attention_decode_dist(p, x, cache, pos, cfg, ctx: AxisCtx, kv_l, seq_shards):
    """Distributed-cache decode: cache sharded (kv-group x seq-chunk) over
    the model axis; partial online-softmax combined with an exp-weighted
    psum.  Requires wk/wv to hold ALL kv heads on every rank (they are
    replicated whenever kv %% tp != 0, see gqa_shapes/attention_tp_axes)."""
    b = x.shape[0]
    hd = cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    g = KV // kv_l  # head groups
    rank = ctx.model_rank()
    kv_grp = rank // seq_shards
    seq_idx = rank % seq_shards
    positions = jnp.broadcast_to(jnp.asarray(pos)[None], (b, 1))
    q, k, v = _project_qkv(p, x, cfg, ctx, positions)
    h_local, _, replicated = gqa_shapes(cfg.d_model, H, KV, hd, ctx.tp)
    # 1. full query heads on every rank
    if replicated:
        q_full = q  # [B,1,H,hd]
    else:
        qg = jax.lax.all_gather(q, ctx.model_axis, axis=2, tiled=True)
        q_full = qg  # [B,1,H,hd]
    hg = H // g  # q heads per kv group
    q_grp = jax.lax.dynamic_slice_in_dim(q_full, kv_grp * hg, hg, axis=2)
    # 2. my kv-head slice of the new token (wk/wv replicated -> k has all KV)
    k_my = jax.lax.dynamic_slice_in_dim(k, kv_grp * kv_l, kv_l, axis=2)
    v_my = jax.lax.dynamic_slice_in_dim(v, kv_grp * kv_l, kv_l, axis=2)
    # 3. write into my chunk if my seq chunk owns the slot
    window = getattr(cfg, "sliding_window", None)
    cache_len = cache["k"].shape[1]
    ring = seq_shards * cache_len
    gslot_new = jnp.mod(pos, ring) if window else pos
    owner = jnp.mod(gslot_new, seq_shards)  # strided ownership
    lslot = gslot_new // seq_shards
    mine = owner == seq_idx
    # conditional write without copying the whole cache: read the old
    # slot (tiny), select, and write back unconditionally — keeps the
    # cache update a single dynamic-update-slice chain XLA can alias.
    old_k = jax.lax.dynamic_slice(cache["k"], (0, lslot, 0, 0), k_my.shape)
    old_v = jax.lax.dynamic_slice(cache["v"], (0, lslot, 0, 0), v_my.shape)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], jnp.where(mine, k_my.astype(cache["k"].dtype), old_k),
        (0, lslot, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], jnp.where(mine, v_my.astype(cache["v"].dtype), old_v),
        (0, lslot, 0, 0))
    # 4. partial attention of my group's q heads over my (heads, seq) chunk
    gslot, valid = _dist_slot_validity(pos, cache_len, seq_idx, window, seq_shards)
    kk, vv = ck, cv
    if kv_l != hg:
        rep = hg // kv_l
        kk = jnp.repeat(kk, rep, axis=2)
        vv = jnp.repeat(vv, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q_grp.astype(jnp.float32),
                        kk.astype(jnp.float32)) / math.sqrt(hd)
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    m_loc = jnp.max(logits, axis=-1)  # [B,hg,1]
    w = jnp.exp(logits - m_loc[..., None])
    l_loc = jnp.sum(w, axis=-1)
    acc_loc = jnp.einsum("bhqk,bkhd->bhqd", w, vv.astype(jnp.float32))
    # 5. pad partials to all H heads at this group's range and psum-combine
    def pad_heads(t):
        z = jnp.zeros(t.shape[:1] + (H,) + t.shape[2:], t.dtype)
        return jax.lax.dynamic_update_slice_in_dim(z, t, kv_grp * hg, axis=1)
    m_pad = pad_heads(jnp.where(l_loc > 0, m_loc, NEG_INF))
    m_star = jax.lax.pmax(m_pad, ctx.model_axis)
    scale_ = jnp.exp(m_pad - m_star)
    l_comb = jax.lax.psum(pad_heads(l_loc) * scale_, ctx.model_axis)
    acc_comb = jax.lax.psum(pad_heads(acc_loc) * scale_[..., None],
                            ctx.model_axis)
    out_full = acc_comb / jnp.maximum(l_comb[..., None], 1e-30)  # [B,H,1,hd]
    # 6. output projection with my wo slice
    if replicated:
        out = out_full.transpose(0, 2, 1, 3).reshape(b, 1, H * hd)
        y = matmul(out.astype(x.dtype), p["wo"], jnp.float32)
    else:
        my = jax.lax.dynamic_slice_in_dim(out_full, rank * h_local, h_local,
                                          axis=1)
        out = my.transpose(0, 2, 1, 3).reshape(b, 1, h_local * hd)
        y = ctx.psum_model(matmul(out.astype(x.dtype), p["wo"], jnp.float32))
    return y.astype(x.dtype), {"k": ck, "v": cv}


def _decode_attend(q, k, v, valid_mask):
    """q: [B,1,H,D]; k/v: [B,C,KV,D]; valid_mask: [C] bool."""
    b, _, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(d)
    logits = jnp.where(valid_mask[None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP (gated / plain), column+row parallel
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, tp: int, dtype=jnp.float32) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if f % tp != 0:
        raise ValueError(f"d_ff={f} not divisible by tp={tp}")
    f_l = f // tp
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d, f_l), dtype=dtype),
         "w_down": dense_init(ks[1], (f_l, d), dtype=dtype)}
    if getattr(cfg, "gated_mlp", True):
        p["w_gate"] = dense_init(ks[2], (d, f_l), dtype=dtype)
    return p


def mlp_tp_axes(cfg) -> dict:
    axes = {"w_up": 1, "w_down": 0}
    if getattr(cfg, "gated_mlp", True):
        axes["w_gate"] = 1
    return axes


def mlp_fwd(p, x, cfg, ctx: AxisCtx):
    act = ACTIVATIONS[getattr(cfg, "activation", "silu")]
    up = matmul(x, p["w_up"])
    if "w_gate" in p:
        h = act(matmul(x, p["w_gate"])) * up
    else:
        h = act(up)
    return ctx.psum_model(matmul(h, p["w_down"], jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# vocab-parallel embedding / head / cross-entropy
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, tp: int, dtype=jnp.float32) -> dict:
    if vocab % tp != 0:
        vocab_l = -(-vocab // tp)
    else:
        vocab_l = vocab // tp
    return {"table": dense_init(key, (vocab_l, d_model), in_axis=1, dtype=dtype)}


def embedding_tp_axes() -> dict:
    return {"table": 0}


def embed_lookup(p, ids, vocab: int, ctx: AxisCtx):
    """Vocab-parallel lookup: one-hot over the local vocab shard, psum."""
    table = p["table"]
    vocab_l = table.shape[0]
    start = ctx.model_rank() * vocab_l
    local_ids = ids - start
    in_range = (local_ids >= 0) & (local_ids < vocab_l)
    safe = jnp.where(in_range, local_ids, 0)
    emb = jnp.take(table, safe, axis=0)
    emb = jnp.where(in_range[..., None], emb, 0)
    return ctx.psum_model(emb.astype(jnp.float32)).astype(table.dtype)


def lm_logits_local(p, x, ctx: AxisCtx):
    """Tied head: x @ table^T -> logits over the LOCAL vocab shard."""
    return jnp.einsum(
        "...d,vd->...v", x, p["table"], preferred_element_type=jnp.float32
    )


def greedy_token(local_logits, vocab: int, ctx: AxisCtx):
    """Argmax across vocab-parallel logits. local_logits: [B,1,V_local].

    Shared by the compiled decode step and the eager serving engine so
    both planes resolve ties identically (max first, then the lowest
    global token id): token-for-token parity between them must not hinge
    on two argmax implementations agreeing.  With ``tp == 1`` the psum /
    pmax degenerate and this is a plain masked argmax."""
    vl = local_logits.shape[-1]
    start = ctx.model_rank() * vl
    gid = start + jnp.arange(vl)
    ll = jnp.where(gid < vocab, local_logits, -jnp.inf)
    lmax = jnp.max(ll, axis=-1)
    lidx = jnp.argmax(ll, axis=-1) + start
    gmax = ctx.pmax_model(lmax)
    cand = jnp.where(lmax >= gmax, lidx, vocab + 1)
    if ctx.model_axis:
        cand = -jax.lax.pmax(-cand, ctx.model_axis)  # pmin
    return cand[..., 0].astype(jnp.int32)  # [B]


def vocab_parallel_xent(local_logits, labels, vocab: int, ctx: AxisCtx, *, mask=None):
    """Cross-entropy over a vocab-sharded logits tensor without gathering.

    local_logits: [..., V_local] fp32; labels: [...] int32 (global ids).
    Returns per-position loss [...]; psum over model is internal.
    """
    vocab_l = local_logits.shape[-1]
    start = ctx.model_rank() * vocab_l
    # mask padded vocab rows (vocab not divisible by tp)
    gid = start + jnp.arange(vocab_l)
    local_logits = jnp.where(gid < vocab, local_logits, NEG_INF)
    local_max = jax.lax.stop_gradient(jnp.max(local_logits, axis=-1))
    gmax = ctx.pmax_model(local_max)  # stop-grad'd: max-shift only
    z = jnp.sum(jnp.exp(local_logits - gmax[..., None]), axis=-1)
    gz = ctx.psum_model(z)
    lse = jnp.log(gz) + gmax
    local_labels = labels - start
    in_range = (local_labels >= 0) & (local_labels < vocab_l)
    safe = jnp.where(in_range, local_labels, 0)
    picked = jnp.take_along_axis(local_logits, safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)
    target_logit = ctx.psum_model(picked)
    loss = lse - target_logit
    if mask is not None:
        loss = loss * mask
    return loss


def blockwise_xent_sum(table_p, x, labels, vocab: int, ctx: AxisCtx,
                       block: int, mask=None):
    """Sum of vocab-parallel xent over [B,S] positions, computed in
    sequence blocks so the fp32 [tokens, V_local] logits never fully
    materialize (§Perf memory-term optimization for the LM head)."""
    b, s, d = x.shape
    nb = -(-s // block)
    pad = nb * block - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        pm = jnp.pad(jnp.ones((b, s), jnp.float32) if mask is None else mask,
                     ((0, 0), (0, pad)))
    else:
        pm = jnp.ones((b, s), jnp.float32) if mask is None else mask
    xb = x.reshape(b, nb, block, d).transpose(1, 0, 2, 3)
    lb = labels.reshape(b, nb, block).transpose(1, 0, 2)
    mb = pm.reshape(b, nb, block).transpose(1, 0, 2)
    va = all_axes(ctx)

    def body(acc, inp):
        xi, li, mi = inp
        logits = lm_logits_local(table_p, xi, ctx)
        per_tok = vocab_parallel_xent(logits, li, vocab, ctx, mask=mi)
        return vary_to(acc + jnp.sum(per_tok), va), None

    body = jax.checkpoint(body)
    acc, _ = jax.lax.scan(body, vary_to(jnp.float32(0.0), va), (xb, lb, mb))
    return acc
