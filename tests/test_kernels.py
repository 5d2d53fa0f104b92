"""Pallas kernels vs their jnp oracles (interpret mode), shape/dtype sweep."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.chunked_adam import BLOCK, chunked_adam_kernel
from repro.kernels.flash_attention import flash_attention_kernel


pytestmark = pytest.mark.kernels  # whole module: the kernel-sweep CI job


@pytest.mark.parametrize("n_blocks", [1, 3])
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("wd", [0.0, 0.1])
def test_chunked_adam_sweep(n_blocks, gdtype, wd):
    n = BLOCK * n_blocks
    k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
    p32 = jax.random.normal(k1, (n,))
    m = jax.random.normal(k2, (n,)) * 0.01
    v = jnp.abs(jax.random.normal(k3, (n,))) * 0.01
    g = jax.random.normal(k4, (n,)).astype(gdtype)
    hp = dict(lr=3e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=wd,
              bias_corr1=0.1, bias_corr2=0.05)
    got = chunked_adam_kernel(p32, m, v, g, interpret=True, **hp)
    want = ref.adam_ref(p32, m, v, g, **hp)
    for a, b, name in zip(got[:3], want, "pmv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6, err_msg=name)
    # fused bf16 conversion of the updated params
    np.testing.assert_allclose(np.asarray(got[3].astype(jnp.float32)),
                               np.asarray(want[0]), rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("b,s,h,d,bq,bk", [
    (1, 128, 2, 64, 64, 64),
    (2, 256, 4, 64, 64, 128),
    (1, 256, 1, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, h, d, bq, bk, dtype, causal):
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(k1, (b, s, h, d), dtype)
    k = jax.random.normal(k2, (b, s, h, d), dtype)
    v = jax.random.normal(k3, (b, s, h, d), dtype)
    got = flash_attention_kernel(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_scan_twin():
    """The Pallas kernel and the jnp scan twin implement the same math."""
    from repro.models.layers import scan_attention
    k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(k1, (2, 128, 2, 64))
    k = jax.random.normal(k2, (2, 128, 2, 64))
    v = jax.random.normal(k3, (2, 128, 2, 64))
    a = flash_attention_kernel(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)
    b = scan_attention(q, k, v, causal=True, block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def _adam_args():
    k1, k2, k3, k4 = jax.random.split(jax.random.key(3), 4)
    shape = (3, 1, 2 * BLOCK)  # a [G, p, S] chunk store
    p32 = jax.random.normal(k1, shape)
    m = jax.random.normal(k2, shape) * 0.01
    v = jnp.abs(jax.random.normal(k3, shape)) * 0.01
    g = jax.random.normal(k4, shape).astype(jnp.bfloat16)
    hp = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.0,
              bias_corr1=0.1, bias_corr2=0.05)
    return (p32, m, v, g), hp


def _flash_args():
    k1, k2, k3 = jax.random.split(jax.random.key(4), 3)
    return tuple(jax.random.normal(k, (1, 512, 2, 64)) for k in (k1, k2, k3)), {}


_OPS = {
    "chunked_adam": (_adam_args, ref.adam_ref),
    "flash_attention": (_flash_args, ref.flash_attention_ref),
}


@pytest.mark.parametrize("name", sorted(_OPS))
def test_ops_refuse_a_non_tpu_backend(name):
    """The public wrappers never swap in a reference or the interpreter:
    off TPU, a call without ``interpret=True`` raises."""
    from repro.kernels import ops

    assert jax.default_backend() != "tpu"
    args, kw = _OPS[name][0]()
    with pytest.raises(RuntimeError, match="interpret=True"):
        getattr(ops, name)(*args, **kw)


@pytest.mark.parametrize("name", sorted(_OPS))
def test_ops_interpret_matches_ref(name):
    from repro.kernels import ops

    args, kw = _OPS[name][0]()
    got = getattr(ops, name)(*args, interpret=True, **kw)
    want = _OPS[name][1](*args, **kw)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
