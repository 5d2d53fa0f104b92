"""shard_map/jit wrappers, state init, and input specs for the runtime.

This is the layer the launcher and the dry-run call: it turns the local
step functions from ``runtime.step`` into jitted global-array functions
with explicit NamedShardings (including ``pinned_host`` memory kinds for
host-resident optimizer-state chunk groups).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, dtype_of
from repro.core import zero
from repro.runtime.step import ChunkedRuntime


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def batch_axes(rt: ChunkedRuntime, global_batch: int):
    """Mesh axes the batch dim shards over (must divide evenly)."""
    axes = []
    n = 1
    if rt.ctx.pods > 1 and global_batch % (rt.ctx.pods * rt.ctx.dp) == 0:
        axes.append("pod")
        n *= rt.ctx.pods
    if rt.ctx.dp > 1 and global_batch % (n * rt.ctx.dp) == 0:
        axes.append("data")
    if not axes:
        return None  # replicate (e.g. batch=1 long-context decode)
    return tuple(axes)


def _ns(rt, spec, *, host=False):
    kind = rt.host_memory_kind if host else None
    return NamedSharding(rt.mesh, spec, memory_kind=kind)


def os_shardings(rt: ChunkedRuntime):
    out = {}
    for name, pspec in rt.store_pspecs().items():
        out[name] = {k: {"dev": _ns(rt, pspec),
                         "host": _ns(rt, pspec, host=True)}
                     for k in ("p32", "m", "v")}
    return out


def param_shardings(rt: ChunkedRuntime):
    return {name: _ns(rt, pspec) for name, pspec in rt.store_pspecs().items()}


# ---------------------------------------------------------------------------
# input specs per (arch, input shape)  — ShapeDtypeStructs, no allocation
# ---------------------------------------------------------------------------


def train_batch_specs(rt: ChunkedRuntime, shape: InputShape):
    cfg = rt.cfg
    b, s = shape.global_batch, shape.seq_len
    ba = batch_axes(rt, b)
    tok = lambda shp: jax.ShapeDtypeStruct(shp, jnp.int32)
    if cfg.arch_type == "audio":
        frames = min(cfg.encoder_frames, s)
        specs = {
            "frames": jax.ShapeDtypeStruct(
                (b, frames, cfg.frontend_dim), jnp.float32),
            "tokens": tok((b, s)), "labels": tok((b, s)),
        }
        pspecs = {"frames": P(ba, None, None),
                  "tokens": P(ba, None), "labels": P(ba, None)}
        n_tokens = b * s
    elif cfg.arch_type == "vlm":
        p_ = cfg.num_patches
        st = s - p_
        specs = {
            "patch_embeds": jax.ShapeDtypeStruct((b, p_, cfg.vision_dim), jnp.float32),
            "tokens": tok((b, st)), "labels": tok((b, st)),
        }
        pspecs = {"patch_embeds": P(ba, None, None),
                  "tokens": P(ba, None), "labels": P(ba, None)}
        n_tokens = b * st
    else:
        specs = {"tokens": tok((b, s)), "labels": tok((b, s))}
        pspecs = {"tokens": P(ba, None), "labels": P(ba, None)}
        n_tokens = b * s
    specs["global_tokens"] = jax.ShapeDtypeStruct((), jnp.float32)
    pspecs["global_tokens"] = P()
    return specs, pspecs, float(n_tokens)


def cache_specs(rt: ChunkedRuntime, shape: InputShape):
    """Global decode-cache ShapeDtypeStructs + PartitionSpecs.

    Layout: [tp, L, B, ...] — tp shards over model, B over (pod, data).
    """
    b, s = shape.global_batch, shape.seq_len
    ba = batch_axes(rt, b)
    tp = rt.ctx.tp
    specs, pspecs = {}, {}
    for g in rt.model.groups():
        if g.init_cache is None or g.decode is None:
            continue
        one = jax.eval_shape(lambda: g.init_cache(b, s))
        L = g.length

        def to_global(sds):
            return jax.ShapeDtypeStruct((tp, L) + sds.shape, sds.dtype)

        def to_pspec(sds):
            # locate the batch dim (hybrid/xlstm caches carry extra
            # leading stacked dims before it); shard it over (pod, data)
            dims = [None] * len(sds.shape)
            if ba is not None:
                for i, d in enumerate(sds.shape):
                    if d == b:
                        dims[i] = ba
                        break
            return P("model", None, *dims)

        specs[g.name] = jax.tree.map(to_global, one)
        pspecs[g.name] = jax.tree.map(to_pspec, one)
    return specs, pspecs


def decode_input_specs(rt: ChunkedRuntime, shape: InputShape):
    b = shape.global_batch
    ba = batch_axes(rt, b)
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    caches, cache_ps = cache_specs(rt, shape)
    return {
        "token": (token, P(ba, None)),
        "pos": (jax.ShapeDtypeStruct((), jnp.int32), P()),
        "caches": (caches, cache_ps),
    }


# ---------------------------------------------------------------------------
# jitted global-step builders
# ---------------------------------------------------------------------------


def _smap(rt, fn, in_specs, out_specs, *, check_vma=True):
    # check_vma=True is required for correct psum/pcast gradient
    # transposes in training; serve paths (no autodiff) run with it off,
    # since batch-replicated decode (global_batch=1) produces values that
    # are invariant in fact but typed varying.
    return jax.shard_map(fn, mesh=rt.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def build_train_step(rt: ChunkedRuntime, shape: InputShape):
    """-> (jitted step, arg ShapeDtypeStructs, arg shardings)."""
    step = rt.train_step_fn()
    bspecs, bpspecs, _ = train_batch_specs(rt, shape)
    p_ps = rt.store_pspecs()
    os_ps = rt.os_pspecs()
    metrics_ps = {"loss": P(), "aux_loss": P()}
    f = _smap(rt, step, (p_ps, os_ps, bpspecs, P()),
              (p_ps, os_ps, metrics_ps))
    in_shardings = (param_shardings(rt), os_shardings(rt),
                    jax.tree.map(lambda ps: _ns(rt, ps), bpspecs,
                                 is_leaf=lambda x: isinstance(x, P)),
                    _ns(rt, P()))
    out_shardings = (param_shardings(rt), os_shardings(rt),
                     jax.tree.map(lambda ps: _ns(rt, ps), metrics_ps,
                                  is_leaf=lambda x: isinstance(x, P)))
    jf = jax.jit(f, in_shardings=in_shardings, out_shardings=out_shardings,
                 donate_argnums=(0, 1))
    # the specs carry their shardings (memory kinds included), so a
    # lowering from them types the host-resident stores as a call would
    args = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        (rt.store_specs(), rt.os_specs(), bspecs,
         jax.ShapeDtypeStruct((), jnp.int32)), in_shardings)
    return jf, args, in_shardings


def build_prefill_step(rt: ChunkedRuntime, shape: InputShape):
    step = rt.prefill_step_fn()
    cfg = rt.cfg
    b, s = shape.global_batch, shape.seq_len
    ba = batch_axes(rt, b)
    if cfg.arch_type == "audio":
        frames = min(cfg.encoder_frames, 1500)
        bspecs = {"frames": jax.ShapeDtypeStruct((b, frames, cfg.frontend_dim),
                                                 jnp.float32),
                  "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        bpspecs = {"frames": P(ba, None, None), "tokens": P(ba, None)}
    elif cfg.arch_type == "vlm":
        bspecs = {"patch_embeds": jax.ShapeDtypeStruct(
                      (b, cfg.num_patches, cfg.vision_dim), jnp.float32),
                  "tokens": jax.ShapeDtypeStruct((b, s - cfg.num_patches), jnp.int32)}
        bpspecs = {"patch_embeds": P(ba, None, None), "tokens": P(ba, None)}
    else:
        bspecs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        bpspecs = {"tokens": P(ba, None)}
    _, cache_ps = cache_specs(rt, shape)
    p_ps = rt.store_pspecs()
    logits_ps = P(ba, None, "model")
    f = _smap(rt, step, (p_ps, bpspecs), (logits_ps, cache_ps),
              check_vma=False)
    jf = jax.jit(f, in_shardings=(param_shardings(rt),
                                  jax.tree.map(lambda ps: _ns(rt, ps), bpspecs,
                                               is_leaf=lambda x: isinstance(x, P))))
    return jf, (rt.store_specs(), bspecs)


def build_decode_step(rt: ChunkedRuntime, shape: InputShape):
    step = rt.decode_step_fn()
    di = decode_input_specs(rt, shape)
    b = shape.global_batch
    ba = batch_axes(rt, b)
    p_ps = rt.store_pspecs()
    cache_ps = di["caches"][1]
    f = _smap(rt, step,
              (p_ps, cache_ps, di["token"][1], P()),
              (P(ba), cache_ps), check_vma=False)
    in_sh = (param_shardings(rt),
             jax.tree.map(lambda ps: _ns(rt, ps), cache_ps,
                          is_leaf=lambda x: isinstance(x, P)),
             _ns(rt, di["token"][1]), _ns(rt, P()))
    jf = jax.jit(f, in_shardings=in_sh, donate_argnums=(1,))
    args = (rt.store_specs(), di["caches"][0], di["token"][0], di["pos"][0])
    return jf, args


def round_cache_specs(rt: ChunkedRuntime, slots: int, horizon: int):
    """Slot-cache ShapeDtypeStructs + PartitionSpecs for the compiled
    serving round.

    Layout: [tp, L, S_slots, ...per-seq cache...] — every leaf is the
    lane-stacked single-sequence cache (batch dim 1 *inside* the per-seq
    shape, wherever the arch puts it), so the same layout serves archs
    with non-batch-leading cache leaves.  The slot axis is replicated:
    serving runs host-driven, one process.
    """
    tp = rt.ctx.tp
    specs, pspecs = {}, {}
    for g in rt.model.groups():
        if g.init_cache is None or g.decode is None:
            continue
        one = jax.eval_shape(lambda _g=g: _g.init_cache(1, horizon))
        L = g.length
        specs[g.name] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((tp, L, slots) + s.shape, s.dtype),
            one)
        pspecs[g.name] = jax.tree.map(
            lambda s: P("model", None, None, *([None] * len(s.shape))), one)
    return specs, pspecs


def build_round_decode_step(rt: ChunkedRuntime, slots: int, horizon: int):
    """-> (jitted round decode step, slot-cache ShapeDtypeStructs).

    ``step(pstores, caches, tokens [S,1], pos [S]) -> (tokens [S],
    caches)`` — ONE compiled call advances every padded slot from its own
    position.  Compilation keys only on the padded slot count (and
    horizon): membership changes within a padded shape never recompile.
    """
    step = rt.round_decode_step_fn()
    specs, cache_ps = round_cache_specs(rt, slots, horizon)
    p_ps = rt.store_pspecs()
    f = _smap(rt, step, (p_ps, cache_ps, P(None, None), P(None)),
              (P(None), cache_ps), check_vma=False)
    jf = jax.jit(f, donate_argnums=(1,))
    return jf, specs


def build_round_prefill_step(rt: ChunkedRuntime, cohort: int, prompt_len: int):
    """-> jitted cohort prefill: ``step(pstores, tokens [K, S_prompt]) ->
    (first_tokens [K], caches)`` with lane-stacked cache leaves
    [tp, L, K, ...].  Compilation keys on (padded cohort, prompt length)."""
    step = rt.round_prefill_step_fn()
    # prefill emits the same cache *structure* as init_cache with
    # prompt-length-dependent leaf shapes; the P specs only need ranks,
    # which match the init template leaf for leaf
    _, cache_ps = round_cache_specs(rt, cohort, prompt_len)
    p_ps = rt.store_pspecs()
    f = _smap(rt, step, (p_ps, P(None, None)), (P(None), cache_ps),
              check_vma=False)
    return jax.jit(f)


def slot_page_range(slot: int, total_layers: int,
                    pages_per_slot: int) -> range:
    """Chunk-id range padded batch slot ``slot`` pins its kv pages into:
    ``pages_per_slot`` ids per flattened layer, slots laid out
    contiguously.  With one page per slot (unpaged horizon) this is the
    historical ``[slot*total_layers, (slot+1)*total_layers)`` binding."""
    w = total_layers * pages_per_slot
    return range(slot * w, (slot + 1) * w)


def slot_page_chunk_id(slot: int, total_layers: int, pages_per_slot: int,
                       flat_layer: int, page: int) -> int:
    """Chunk id of one (slot, layer, page) kv tensor inside
    :func:`slot_page_range` — layer-major, page-minor, so a layer's pages
    are contiguous."""
    return (slot * total_layers * pages_per_slot
            + flat_layer * pages_per_slot + page)


# ---------------------------------------------------------------------------
# state init (for real runs — examples / integration tests)
# ---------------------------------------------------------------------------


def init_state(rt: ChunkedRuntime, key):
    """Materialize param + optimizer-state chunk stores on the mesh."""
    return build_init_state(rt)(key)


def build_init_state(rt: ChunkedRuntime):
    """-> jitted ``f(key) -> (pstores, osstores)`` placing every store with
    its sharding (host-resident OS groups in pinned_host)."""
    ctx = rt.ctx

    def local_init(key):
        # Sharded leaves draw per-model-rank randomness (their shards are
        # disjoint pieces of one logical tensor); REPLICATED leaves must
        # be bitwise identical across model ranks (router, MLA latent
        # projections, replicated kv, ...) — init both ways, select by
        # tp_axes.
        stem_r, layers_r = rt.model.param_keys(
            jax.random.fold_in(key, ctx.model_rank()))
        stem_s, layers_s = rt.model.param_keys(key)

        def select(axes, ranked, shared):
            return jax.tree.map(
                lambda ax, a, b: b if ax is None else a,
                axes, ranked, shared, is_leaf=lambda x: x is None)

        drank = (jax.lax.axis_index(ctx.data_axis)
                 if ctx.data_axis and ctx.dp > 1 else 0)

        def local_store(lay, params):
            store = zero.flatten_to_store(lay, params)  # [G, p, S]
            return jax.lax.dynamic_slice_in_dim(store, drank, 1, axis=1)

        stem = select(rt.tp_axes["stem"], rt.model.init_stem(stem_r),
                      rt.model.init_stem(stem_s))
        pstores = {"stem": local_store(rt.layouts["stem"], stem)[None]}
        # one layer at a time (the same keys init_params vmaps over): the
        # loop body compiles once, where a vmap over L does not
        for g in rt.model.groups():
            def layer(keys, _g=g):
                params = select(rt.tp_axes["groups"][_g.name],
                                _g.init_layer(keys[0]), _g.init_layer(keys[1]))
                return local_store(rt.layouts[_g.name], params)
            pstores[g.name] = jax.lax.map(
                layer, (layers_r[g.name], layers_s[g.name]))[None]

        # OS stores start as (p32 = bf16 params, m = v = 0), built one
        # slice at a time (a layer of a group store, a chunk group of the
        # stem) and spilled as made, so no fp32 store materialises in HBM
        def os_part(p, part):
            def body(_, x):
                p32 = x.astype(jnp.float32)
                zeros = jnp.zeros_like(p32)
                return None, tuple(rt.spill(y, part) for y in (p32, zeros, zeros))
            _, ys = jax.lax.scan(body, None, p.reshape(p.shape[1:]))
            return tuple(y.reshape((1,) + y.shape) for y in ys)

        osstores = {}
        for name, p in pstores.items():
            # local stores keep the global rank ([1(tp), ..., G, 1, S]),
            # so the G axis index matches the global one
            gax = 1 if name == "stem" else 2
            dev_g, host_g = rt.os_split(name)
            parts = {
                part: os_part(jax.lax.slice_in_dim(p, lo, hi, axis=gax), part)
                for part, lo, hi in (("dev", 0, dev_g),
                                     ("host", dev_g, dev_g + host_g))}
            osstores[name] = {k: {part: parts[part][i] for part in parts}
                              for i, k in enumerate(("p32", "m", "v"))}
        return pstores, osstores

    p_ps = rt.store_pspecs()
    os_ps = rt.os_pspecs()
    f = _smap(rt, local_init, (P(),), (p_ps, os_ps))
    return jax.jit(f, out_shardings=(param_shardings(rt), os_shardings(rt)))


def init_caches(rt: ChunkedRuntime, shape: InputShape):
    """Materialize zero-filled decode caches (for real decode runs)."""
    specs, pspecs = cache_specs(rt, shape)
    b, s = shape.global_batch, shape.seq_len
    ba = batch_axes(rt, b) or ()
    shard = 1
    for a in ba:
        shard *= rt.mesh.shape[a]
    b_local = b // shard

    def make():
        out = {}
        for g in rt.model.groups():
            if g.name not in specs:
                continue
            one = g.init_cache(b_local, s)
            L = g.length
            out[g.name] = jax.tree.map(
                lambda t: jnp.broadcast_to(t[None], (L,) + t.shape)[None], one)
        return out

    jf = jax.jit(_smap_nullary(rt, make, pspecs))
    return jf()


def _smap_nullary(rt, fn, out_specs):
    def wrapper(dummy):
        return fn()
    return functools.partial(
        jax.shard_map(wrapper, mesh=rt.mesh, in_specs=(P(),),
                      out_specs=out_specs, check_vma=True),
        jnp.zeros((), jnp.int32))


def grow_caches(rt: ChunkedRuntime, caches, prefill_len: int, horizon: int,
                decode_shape: InputShape):
    """Pad prefill-emitted caches to a decode horizon.

    Distributed caches use STRIDED slot ownership (slot s -> rank
    s % seq_shards at local index s // seq_shards), so growing the horizon
    is a pure local pad along the per-rank slot axis — no cross-rank
    reshuffle.  State-style caches (SSM/mLSTM, no slot axis) pass through
    untouched: their shapes are horizon-independent.
    """
    target, _ = cache_specs(rt, decode_shape)

    def pad(cur, tgt):
        if cur.shape == tgt.shape:
            return cur
        pads = []
        for a, b in zip(cur.shape, tgt.shape):
            if b < a:
                raise ValueError(f"cannot shrink cache {cur.shape}->{tgt.shape}")
            pads.append((0, b - a))
        return jnp.pad(cur, pads)

    return jax.tree.map(pad, caches, target,
                        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(
                            x, jax.ShapeDtypeStruct))
