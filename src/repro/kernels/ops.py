"""Jitted public wrappers around the Pallas kernels.

The kernels are compiled for TPU.  On any other backend a call raises
unless it asks for the Pallas interpreter (``interpret=True``): nothing
here swaps in a reference or the interpreter behind the caller's back.
The jnp oracles live in ``kernels.ref``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.chunked_adam import BLOCK, chunked_adam_kernel
from repro.kernels.flash_attention import flash_attention_kernel


def _check_backend(name: str, interpret: bool) -> None:
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise RuntimeError(
            f"{name}: the Pallas kernel is compiled for TPU and the backend "
            f"is {backend!r}; pass interpret=True to run it in the Pallas "
            f"interpreter")


def chunked_adam(p32, m, v, g, *, lr, beta1, beta2, eps, weight_decay,
                 bias_corr1, bias_corr2, interpret: bool = False):
    """Fused ADAM over chunk stores of any shape.

    Pads the flattened store to the kernel block size and runs the Pallas
    kernel.  Returns (p32', m', v') matching the input shape; the bf16
    conversion happens in the caller.
    """
    _check_backend("chunked_adam", interpret)
    shape = p32.shape
    n = p32.size
    pad = (-n) % BLOCK
    flat = lambda x: jnp.pad(x.reshape(-1), (0, pad))
    p32f, mf, vf, _ = chunked_adam_kernel(
        flat(p32), flat(m), flat(v), flat(g), lr=lr, beta1=beta1,
        beta2=beta2, eps=eps, weight_decay=weight_decay,
        bias_corr1=bias_corr1, bias_corr2=bias_corr2, interpret=interpret)
    unflat = lambda x: x[:n].reshape(shape)
    return unflat(p32f), unflat(mf), unflat(vf)


def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False):
    """[B,S,H,D] causal/full attention through the Pallas flash kernel."""
    _check_backend("flash_attention", interpret)
    return flash_attention_kernel(q, k, v, causal=causal, interpret=interpret)
