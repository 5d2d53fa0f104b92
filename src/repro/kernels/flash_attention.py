"""Flash-attention Pallas TPU kernel (forward).

Online-softmax blocked attention: grid over (batch*heads, q blocks); the
kernel loops over KV blocks with ``jax.lax.fori_loop``, keeping the
running (acc, m, l) in VMEM scratch.  Block sizes default to (128, 512)
— q-block rows fill the MXU's 128 dim, kv blocks stream through VMEM at
512*head_dim*2B per tile.

This is the TPU-native adaptation of the paper's "move data in large
fixed-size blocks" insight applied to the attention hot spot: HBM->VMEM
traffic is exactly one pass over K/V per q block, with no [S, S] score
materialization.  The train/prefill paths use the jnp scan twin
(``models.layers.scan_attention``) for XLA portability; this kernel is
the TPU drop-in validated against the same oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k,
                  seq_k, causal, scale):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, seq_k, d]; o_ref: [1, block_q, d]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    d = q.shape[-1]
    nkv = seq_k // block_k

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        logits = q @ k.astype(jnp.float32).T  # [bq, bk]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(kpos <= qpos, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc_new, m_new, l_new

    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    # causal: kv blocks beyond this q block's diagonal contribute nothing
    if causal:
        upper = jnp.minimum(
            jax.lax.div((qi + 1) * block_q + block_k - 1, block_k), nkv)
    else:
        upper = nkv
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc, m, l))
    o_ref[0] = (acc / jnp.maximum(l[:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 512,
                           interpret: bool = False):
    """q/k/v: [B, S, H, D] (same H; GQA repeat upstream). Returns [B,S,H,D]."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = 1.0 / math.sqrt(d)
    # fold batch and heads into the grid's leading axis
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    grid = (b * h, sq // block_q)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, seq_k=sk, causal=causal,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
