"""Model API: how architectures plug into the chunked runtime.

A model is a **stem** plus an ordered list of **block groups**:

* The *stem* holds everything used at step scope: token embedding / LM
  head (vocab-parallel), final norm, modality projectors, and any params
  **shared across layers** (e.g. Zamba2's shared attention block — the
  paper's refcount>1 tensors).  Stem chunks are fetched once per step.
* Each *block group* is a stack of ``length`` structurally identical
  layers executed with ``jax.lax.scan``; its params are stored stacked
  ``[L, ...]`` and chunk-managed per layer, so the distributed runtime can
  all-gather exactly one layer's communication groups inside the scan body
  (PatrickStar's per-operator chunk fetch, Section 6.2/7).

The runtime (``launch/train.py``) owns chunking/gathering; models only
describe structure and pure per-layer math.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.layers import AxisCtx


@dataclasses.dataclass(frozen=True)
class BlockGroup:
    """A scanned stack of identical layers."""

    name: str
    length: int
    # init_layer(key) -> TP-local params pytree for ONE layer
    init_layer: Callable[[jax.Array], Any]
    # apply(params, x, extras, ctx) -> x            (training / full-seq)
    apply: Callable[[Any, jax.Array, Any, AxisCtx], jax.Array]
    # init_cache(batch, max_len) -> ONE layer's decode cache
    init_cache: Callable[[int, int], Any] | None = None
    # prefill(params, x, extras, ctx) -> (x, cache)
    prefill: Callable[..., tuple[jax.Array, Any]] | None = None
    # decode(params, x, cache, pos, extras, ctx) -> (x, cache)
    decode: Callable[..., tuple[jax.Array, Any]] | None = None


class Model:
    """Base class; concrete architectures override the hooks below."""

    def __init__(self, cfg: Any, ctx: AxisCtx):
        self.cfg = cfg
        self.ctx = ctx

    # ----------------------------------------------------------- structure
    def init_stem(self, key: jax.Array) -> Any:
        raise NotImplementedError

    def groups(self) -> list[BlockGroup]:
        raise NotImplementedError

    # ------------------------------------------------------------- forward
    def embed(self, stem: Any, batch: dict) -> tuple[jax.Array, Any]:
        """-> (x [B,S,d], extras) — extras are scan-invariant inputs that
        block groups may consume (original embeddings, encoder output,
        shared-block params...)."""
        raise NotImplementedError

    def between_groups(self, name: str, x: jax.Array, extras: Any,
                       stem: Any, batch: dict) -> tuple[jax.Array, Any]:
        """Hook run before group ``name`` (e.g. enc->dec handoff)."""
        return x, extras

    def head_loss(self, stem: Any, x: jax.Array, batch: dict) -> jax.Array:
        """Final norm + LM head + masked mean loss (scalar, LOCAL batch
        sum / GLOBAL token count; the runtime psums across dp)."""
        raise NotImplementedError

    # ------------------------------------------------------------- serving
    def embed_decode(self, stem: Any, token: jax.Array, pos: jax.Array,
                     extras: Any) -> jax.Array:
        """Embed a single decode token -> [B,1,d]."""
        raise NotImplementedError

    def head_logits(self, stem: Any, x: jax.Array) -> jax.Array:
        """-> vocab-LOCAL logits (fp32)."""
        raise NotImplementedError

    def decode_extras(self, stem: Any, x: jax.Array) -> Any:
        """extras for decode-time group applies (default: none)."""
        return None

    # ------------------------------------------------------------ metadata
    @property
    def supports_decode(self) -> bool:
        # encoder-style groups (no cache) are skipped at decode time; the
        # model decodes iff at least one group has a decode step
        return any(g.decode is not None for g in self.groups())

    def init_params(self, key: jax.Array) -> dict:
        """Full (TP-local) param tree: {"stem": ..., groups: {name: stacked}}."""
        stem_key, layer_keys = self.param_keys(key)
        return {"stem": self.init_stem(stem_key),
                "groups": {g.name: jax.vmap(g.init_layer)(layer_keys[g.name])
                           for g in self.groups()}}

    def param_keys(self, key: jax.Array) -> tuple[jax.Array, dict]:
        """-> (stem key, {group name: [length] per-layer keys}) — the keys
        :meth:`init_params` draws from, for callers that initialise one
        layer at a time."""
        keys = jax.random.split(key, 1 + len(self.groups()))
        return keys[0], {g.name: jax.random.split(keys[1 + i], g.length)
                         for i, g in enumerate(self.groups())}

    def param_specs(self) -> dict:
        """ShapeDtypeStructs of the TP-local param tree (no allocation)."""
        return jax.eval_shape(lambda k: self.init_params(k),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))


def count_params(tree: Any) -> int:
    import numpy as np

    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def masked_mean_loss(per_tok_loss: jax.Array, mask: jax.Array | None,
                     global_tokens: float) -> jax.Array:
    """Local loss sum scaled by the GLOBAL token count, so that psum over
    the dp axes yields the true global mean (and grads are correctly
    scaled without a later divide)."""
    if mask is not None:
        per_tok_loss = per_tok_loss * mask
    return jnp.sum(per_tok_loss) / global_tokens
