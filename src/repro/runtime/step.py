"""Distributed chunked runtime: the compiled counterpart of PatrickStar.

Array conventions (GLOBAL shapes; leading axes shard over the mesh):

  param store (stem)    [tp, G, p, S]        P(model, None, data, None)
  param store (group)   [tp, L, G, p, S]     P(model, None, None, data, None)
  optimizer-state store same layout, fp32 (3 of them: p32 / m / v),
                        optionally split along G into a device-resident
                        part and a pinned_host-resident part (Section 8.2)
  batch tensors         [B, ...]             P((pod, data), ...)
  decode caches         [tp, L, B, ...]      P(model, None, (pod,data), ...)

Inside shard_map every block is local; the leading tp/ZeRO axes collapse
to 1 and are squeezed.  Per-layer chunk fetch = ``all_gather`` over
``data`` inside the layer scan (transpose: reduce-scatter of grads);
HOLD_AFTER_FWD semantics = ``jax.checkpoint`` refusing to save gathered
params, so BWD re-gathers (Section 6.2).  ADAM runs on the local shard
only (Section 7: "the ADAM stage is executed locally").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, dtype_of
from repro.core import zero
from repro.core.zero import ChunkLayout
from repro.models import tp as tpmod
from repro.models.api import Model
from repro.models.layers import AxisCtx, all_axes, greedy_token, vary_tree


@dataclasses.dataclass(frozen=True)
class RuntimeOptions:
    remat: str = "full"  # "full" | "dots" | "none"
    gather_policy: str = "layer"  # "layer" | "step"
    chunk_size: int | None = None  # None -> per-layout search
    # fraction of OS chunk groups host-resident (1.0 = ZeRO-Offload-style
    # all-on-host; 0.0 = all-on-device; paper's device-aware placement
    # picks this from margin space)
    os_host_fraction: float = 0.0
    # optimizer
    lr: float = 1e-3
    betas: tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.0
    use_adam_kernel: bool = False  # Pallas fused chunked-Adam
    attn_impl: str = "auto"
    attn_block: int = 512
    # ---- beyond-paper §Perf switches -------------------------------------
    inner_remat: bool = False  # checkpoint inner seq scans (memory term)
    moe_combine_first: bool = False  # combine before psum (collective term)
    # gradient accumulation: split the global batch into N microbatches
    # scanned sequentially (activation memory / N at ~no flops cost)
    accum_steps: int = 1
    xent_block: int = 0  # blockwise LM-head cross-entropy (0 = off)


def map_slices(fn, carry, xs=()):
    """In-place ``carry[k][:, i] = fn(*carry[:, i], *xs[:, i])[k]`` for
    every index ``i`` of axis 1 of local ``[1, N, ...]`` stores (the layers
    of a group store, the chunk groups of the stem).  A ``fori_loop`` over
    ``i`` keeps one slice of each store live in HBM at a time; ``carry``
    may live in pinned_host.  Slices keep their singleton axes."""
    drop = lambda a: a.reshape(a.shape[1:])  # the collapsed tp axis
    xs = [drop(x) for x in xs]

    def body(i, acc):
        sl = lambda a: jax.lax.dynamic_index_in_dim(a, i, axis=0)
        ys = fn(*map(sl, acc), *map(sl, xs))
        return tuple(jax.lax.dynamic_update_index_in_dim(a, y, i, axis=0)
                     for a, y in zip(acc, ys))

    out = jax.lax.fori_loop(0, carry[0].shape[1], body,
                            tuple(map(drop, carry)))
    return tuple(o.reshape((1,) + o.shape) for o in out)


def stream_slices(fn, fetch, spill, stores, outs, xs=(), after=()):
    """``map_slices`` for stores that live off the device: slice ``i + 1``
    of each of ``stores`` is fetched into HBM while slice ``i`` is updated
    and spilled back.  ``fn(*store_slices, *out_slices, *x_slices)``
    returns the new slices of ``stores``, then of ``outs`` (stores in HBM),
    as ``map_slices``'s ``fn`` does for ``carry = (*stores, *outs)``.
    A prologue fetches slice 0 and the last slice is peeled, so nothing is
    fetched that is not updated; HBM holds two slices of each store.
    The first fetch waits for the arrays ``after``.  Each store's fetch
    of slice ``i + 1`` ends before its spill of slice ``i`` starts (XLA
    cannot tell the two apart in one buffer), so what overlaps is one
    store's spill with another store's fetch."""
    drop = lambda a: a.reshape(a.shape[1:])
    stores, outs, xs = ([drop(a) for a in t] for t in (stores, outs, xs))
    k, n = len(stores), stores[0].shape[0]
    # else XLA may start this part's first fetch beside another part's
    # loop, and hold both parts' slices in HBM at once
    stores, _ = jax.lax.optimization_barrier((stores, after))
    sl = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, axis=0)
    put = lambda a, y, i: jax.lax.dynamic_update_index_in_dim(a, y, i, axis=0)

    def update(i, st, ou, cur):
        ys = fn(*cur, *(sl(a, i) for a in ou), *(sl(x, i) for x in xs))
        st = tuple(put(a, spill(y), i) for a, y in zip(st, ys[:k]))
        ou = tuple(put(a, y, i) for a, y in zip(ou, ys[k:]))
        return st, ou

    def body(i, acc):
        st, ou, cur = acc
        nxt = tuple(fetch(sl(a, i + 1)) for a in st)
        return (*update(i, st, ou, cur), nxt)

    first = tuple(fetch(sl(a, 0)) for a in stores)
    st, ou, cur = jax.lax.fori_loop(0, n - 1, body, (tuple(stores), tuple(outs), first))
    st, ou = update(n - 1, st, ou, cur)
    # else XLA moves the reshape above the peeled slice's spill, whose
    # write then indexes axis 1, and a host copy must index the major axis
    st = jax.lax.optimization_barrier(st)
    return tuple(o.reshape((1,) + o.shape) for o in (*st, *ou))


class ChunkedRuntime:
    """Binds (model, mesh, options) into lowered/lowerable step functions."""

    def __init__(self, model_cls, cfg, mesh, options: RuntimeOptions | None = None):
        from repro.launch.mesh import mesh_axes

        self.cfg = cfg
        self.mesh = mesh
        self.opt = options or RuntimeOptions()
        ax = mesh_axes(mesh)
        self.ctx = AxisCtx(
            model_axis=ax["model_axis"], tp=ax["tp"],
            data_axis=ax["data_axis"], dp=ax["dp"],
            pod_axis=ax["pod_axis"], pods=ax["pods"],
            attn_impl=self.opt.attn_impl, attn_block=self.opt.attn_block,
            inner_remat=self.opt.inner_remat,
            moe_combine_first=self.opt.moe_combine_first,
            xent_block=self.opt.xent_block,
        )
        # Memory kind of the host-resident OS groups.  XLA:CPU (the test
        # backend) cannot place buffers in pinned_host, so there — and only
        # there — those groups are ordinary device buffers; on an
        # accelerator a failed pinned_host placement raises.
        platform = mesh.devices.flat[0].platform
        self.host_memory_kind = None if platform == "cpu" else "pinned_host"
        self.model: Model = model_cls(cfg, self.ctx)
        self.tp_axes = self.model.tp_axes()
        self._build_layouts()

    # ------------------------------------------------------------------ layout
    def _build_layouts(self):
        specs = self.model.param_specs()
        pdtype = dtype_of(self.cfg.param_dtype)
        dp = self.ctx.dp
        self.layouts: dict[str, ChunkLayout] = {}
        self.layouts["stem"] = zero.make_layout(
            specs["stem"], nproc=dp, dtype=pdtype, chunk_size=self.opt.chunk_size)
        self.group_lengths: dict[str, int] = {}
        for g in self.model.groups():
            one_layer = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                specs["groups"][g.name])
            self.layouts[g.name] = zero.make_layout(
                one_layer, nproc=dp, dtype=pdtype, chunk_size=self.opt.chunk_size)
            self.group_lengths[g.name] = g.length

    # ---------------------------------------------------------------- shapes
    def store_specs(self) -> dict:
        """Global ShapeDtypeStructs of the bf16 param chunk stores."""
        tp = self.ctx.tp
        out = {}
        for name, lay in self.layouts.items():
            g, p, s = lay.store_shape
            if name == "stem":
                out[name] = jax.ShapeDtypeStruct((tp, g, p, s), lay.dtype)
            else:
                out[name] = jax.ShapeDtypeStruct(
                    (tp, self.group_lengths[name], g, p, s), lay.dtype)
        return out

    def store_pspecs(self) -> dict:
        out = {}
        for name in self.layouts:
            if name == "stem":
                out[name] = P("model", None, "data", None)
            else:
                out[name] = P("model", None, None, "data", None)
        return out

    def os_split(self, name: str) -> tuple[int, int]:
        """(device_groups, host_groups) along G for OS stores (Section 8.2)."""
        g = self.layouts[name].num_groups
        host = int(round(g * self.opt.os_host_fraction))
        host = min(max(host, 0), g)
        return g - host, host

    def pipelined_slices(self) -> dict[str, int]:
        """Per store, the slices a step fetches ahead of their update: the
        host part's loop (``stream_slices``) fetches all but its first
        slice while the one before is updated and spilled.  Zero for a
        store with no host part or a one-slice one (the stem's chunk
        groups when there is one)."""
        out = {}
        for name in self.layouts:
            _, host_g = self.os_split(name)
            n = host_g if name == "stem" else self.group_lengths[name]
            out[name] = n - 1 if host_g else 0
        return out

    def os_specs(self) -> dict:
        """OS stores: {"name": {"p32"|"m"|"v": {"dev": SDS, "host": SDS}}}."""
        out = {}
        for name, spec in self.store_specs().items():
            gax = 1 if name == "stem" else 2
            dev_g, host_g = self.os_split(name)
            def _with_g(n_g):
                shape = list(spec.shape)
                shape[gax] = n_g
                return jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
            out[name] = {k: {"dev": _with_g(dev_g), "host": _with_g(host_g)}
                         for k in ("p32", "m", "v")}
        return out

    def os_pspecs(self) -> dict:
        out = {}
        for name, pspec in self.store_pspecs().items():
            out[name] = {k: {"dev": pspec, "host": pspec} for k in ("p32", "m", "v")}
        return out

    # ------------------------------------------------------- gather plumbing
    @jax.named_scope("gather")
    def _gather_tree(self, name: str, local_store, *, dtype):
        """local_store: [G,1,S] (layer or stem slice) -> param pytree with
        replicated-grad sync applied."""
        lay = self.layouts[name]
        if self.ctx.data_axis:
            flat = zero.gather_store(local_store, self.ctx.data_axis)
        else:
            flat = local_store.reshape(-1)
        params = zero.unflatten_from_flat(lay, flat, dtype=dtype)
        axes = (self.tp_axes["stem"] if name == "stem"
                else self.tp_axes["groups"][name])
        return tpmod.sync_replicated_grads(params, axes, self.ctx.model_axis,
                                           self.ctx.tp)

    def _remat(self, fn):
        if self.opt.remat == "none":
            return fn
        if self.opt.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(fn)

    # ----------------------------------------------------------- local steps
    def _loss_local(self, pstores, batch):
        """Runs inside shard_map. pstores: local stores with leading 1s."""
        model, ctx, cdtype = self.model, self.ctx, dtype_of(self.cfg.compute_dtype)
        stem = self._gather_tree("stem", pstores["stem"][0], dtype=cdtype)
        with jax.named_scope("embed"):
            x, extras = model.embed(stem, batch)
        aux = jnp.float32(0.0)
        for g in model.groups():
            x, extras = model.between_groups(g.name, x, extras, stem, batch)
            store = pstores[g.name][0]  # [L,G,1,S]
            if self.opt.gather_policy == "layer":
                va = all_axes(ctx)
                def body(carry, layer_store, _g=g, _va=va):
                    cx, caux = carry
                    params = self._gather_tree(_g.name, layer_store, dtype=cdtype)
                    with jax.named_scope("layers"):
                        y, a = _g.apply(params, cx, extras, ctx)
                    return vary_tree((y, caux + jnp.float32(a)), _va), None
                (x, aux), _ = jax.lax.scan(self._remat(body),
                                           vary_tree((x, aux), va), store)
            else:  # "step": one gather for the whole group, then scan
                lay = self.layouts[g.name]
                with jax.named_scope("gather"):
                    if ctx.data_axis:
                        flat = zero.gather_store(store, ctx.data_axis)  # [L, G*p*S]
                    else:
                        flat = store.reshape(store.shape[0], -1)
                axes = self.tp_axes["groups"][g.name]

                @jax.named_scope("gather")
                def unflatten_layer(fl, _lay=lay, _axes=axes):
                    params = zero.unflatten_from_flat(_lay, fl, dtype=cdtype)
                    return tpmod.sync_replicated_grads(
                        params, _axes, ctx.model_axis, ctx.tp)

                va = all_axes(ctx)
                def body2(carry, fl, _g=g, _uf=unflatten_layer, _va=va):
                    cx, caux = carry
                    params = _uf(fl)
                    with jax.named_scope("layers"):
                        y, a = _g.apply(params, cx, extras, ctx)
                    return vary_tree((y, caux + jnp.float32(a)), _va), None
                (x, aux), _ = jax.lax.scan(self._remat(body2),
                                           vary_tree((x, aux), va), flat)
        with jax.named_scope("head"):
            loss = self.model.head_loss(stem, x, batch)
        return loss + aux, (loss, aux)

    def train_step_fn(self) -> Callable:
        """Returns f(pstores, osstores, batch, step) -> (pstores', os', metrics),
        to be wrapped in shard_map by the caller (see ``shard_train_step``)."""
        ctx = self.ctx

        # the name is the module's: JAX's compile-cache key leaves op_name
        # metadata out, so a change to the named scopes alone needs a new
        # name, or a shared cache serves the executable with the old op_names
        def train_step(pstores, osstores, batch, step_idx):
            if self.opt.accum_steps > 1:
                loss, aux, grads = self._accum_grads(pstores, batch)
            else:
                (tot, (loss, aux)), grads = jax.value_and_grad(
                    self._loss_local, has_aux=True)(pstores, batch)
            if ctx.pod_axis:
                grads = jax.lax.psum(grads, ctx.pod_axis)
            def metric(x):
                # sum over DP axes (per-shard losses carry 1/global_tokens)
                # and mean over the model axis, whose copies are identical —
                # also types the value invariant for the P() out_spec.
                axes = all_axes(ctx)
                if not axes:
                    return x
                from repro.models.layers import vary_to
                return jax.lax.psum(vary_to(x, axes), axes) / ctx.tp

            metrics = {"loss": metric(loss), "aux_loss": metric(aux)}
            new_p, new_os = self._adam_update(pstores, osstores, grads, step_idx)
            return new_p, new_os, metrics

        return train_step

    def _accum_grads(self, pstores, batch):
        """Gradient accumulation over microbatches (scan over batch
        slices): activation live range shrinks by accum_steps; the loss
        already carries 1/global_tokens, so microbatch grads SUM."""
        n = self.opt.accum_steps
        va = all_axes(self.ctx)
        b_loc = batch["tokens"].shape[0]
        if b_loc % n != 0 or b_loc < n:
            raise ValueError(
                f"accum_steps={n} must divide the per-device batch {b_loc}")

        def slice_mb(i):
            def sl(x):
                if not hasattr(x, "ndim") or x.ndim == 0:
                    return x
                mb = x.shape[0] // n
                return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
            return {k: sl(v) for k, v in batch.items()}

        def body(carry, i):
            loss_a, aux_a, g_a = carry
            (tot, (loss, aux)), g = jax.value_and_grad(
                self._loss_local, has_aux=True)(pstores, slice_mb(i))
            g_a = jax.tree.map(jnp.add, g_a, g)
            return vary_tree((loss_a + loss, aux_a + aux, g_a), va), None

        zeros = jax.tree.map(jnp.zeros_like, pstores)
        init = vary_tree((jnp.float32(0), jnp.float32(0), zeros), va)
        (loss, aux, grads), _ = jax.lax.scan(body, init, jnp.arange(n))
        return loss, aux / n, grads

    # -------------------------------------------------------------- optimizer
    def fetch(self, x):
        """Bring a slice of a host-resident OS part into HBM."""
        if self.host_memory_kind is not None:
            return jax.device_put(x, jax.memory.Space.Device)
        return x

    def spill(self, x, part: str):
        """Return a slice of OS part ``part`` to where that part lives."""
        if part == "host" and self.host_memory_kind is not None:
            return jax.device_put(x, jax.memory.Space.Host)
        return x

    @jax.named_scope("adam")
    def _adam_update(self, pstores, osstores, grads, step_idx):
        """Chunked ADAM on the local shard, one slice (a layer of a group
        store, a chunk group of the stem) at a time: grad-bf16 chunks are
        converted to fp32 per slice (Section 6.2), and host-resident OS
        groups are fetched from and spilled back to pinned_host slice by
        slice (device-aware placement, Section 8.2), the next slice
        fetched while one is updated (``stream_slices``).  HBM holds one
        or two slices' fp32 state, not the store."""
        opt = self.opt
        b1, b2 = opt.betas
        t = step_idx.astype(jnp.float32) + 1.0
        bc1 = 1.0 - jnp.power(b1, t)
        bc2 = 1.0 - jnp.power(b2, t)

        def update_part(p32, m, v, g32):
            if opt.use_adam_kernel:
                from repro.kernels import ops as kops
                return kops.chunked_adam(
                    p32, m, v, g32, lr=opt.lr, beta1=b1, beta2=b2,
                    eps=opt.eps, weight_decay=opt.weight_decay,
                    bias_corr1=bc1, bias_corr2=bc2)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * (g32 * g32)
            mhat = m / bc1
            vhat = v / bc2
            upd = mhat / (jnp.sqrt(vhat) + opt.eps)
            if opt.weight_decay:
                upd = upd + opt.weight_decay * p32
            p32 = p32 - opt.lr * upd
            return p32, m, v

        def update_slice(p32, m, v, p, g):
            p32, m, v = update_part(p32, m, v, g.astype(jnp.float32))
            return p32, m, v, p32.astype(p.dtype)

        new_p, new_os = {}, {}
        # a host part's first fetch waits for the last update of the host
        # part before it, whose spill it overlaps: HBM holds one host
        # part's slices at a time.  A host stem goes first, its one slice
        # spilled while the layers stream
        streamed = ()
        stem_first = lambda n: not (n == "stem" and self.os_split(n)[1])
        for name in sorted(pstores, key=stem_first):
            p = pstores[name]
            gax = 1 if name == "stem" else 2
            os_n = osstores[name]
            dev_g = os_n["p32"]["dev"].shape[gax]
            parts, p_parts = {}, []
            for part, lo, hi in (("dev", 0, dev_g), ("host", dev_g, p.shape[gax])):
                state = tuple(os_n[k][part] for k in ("p32", "m", "v"))
                if hi == lo:  # empty part: nothing to update or concatenate
                    parts[part] = state
                    continue
                sl = lambda x: jax.lax.slice_in_dim(x, lo, hi, axis=gax)
                if part == "host":
                    *parts[part], p_new = stream_slices(
                        update_slice, self.fetch,
                        functools.partial(self.spill, part=part),
                        state, (sl(p),), (sl(grads[name]),), streamed)
                    streamed = p_new
                else:
                    *parts[part], p_new = map_slices(
                        update_slice, (*state, sl(p)), (sl(grads[name]),))
                p_parts.append(p_new)
            new_os[name] = {k: {part: parts[part][i] for part in parts}
                            for i, k in enumerate(("p32", "m", "v"))}
            # updated param fp32 -> param bf16 chunks (next iteration's
            # params); both parts were converted on the device
            new_p[name] = (p_parts[0] if len(p_parts) == 1
                           else jax.lax.concatenate(p_parts, dimension=gax))
        return new_p, new_os

    # --------------------------------------------------------------- serving
    def prefill_step_fn(self) -> Callable:
        ctx, cdtype = self.ctx, dtype_of(self.cfg.compute_dtype)
        model = self.model

        def step(pstores, batch):
            stem = self._gather_tree("stem", pstores["stem"][0], dtype=cdtype)
            x, extras = model.embed(stem, batch)
            caches = {}
            for g in model.groups():
                x, extras = model.between_groups(g.name, x, extras, stem, batch)
                store = pstores[g.name][0]
                fn = g.prefill if g.prefill is not None else None

                va = all_axes(ctx)
                def body(cx, layer_store, _g=g, _fn=fn, _va=va):
                    params = self._gather_tree(_g.name, layer_store, dtype=cdtype)
                    if _fn is None:
                        y, _ = _g.apply(params, cx, extras, ctx)
                        return vary_tree(y, _va), 0
                    y, cache = _fn(params, cx, extras, ctx)
                    return vary_tree(y, _va), vary_tree(cache, _va)
                x, ys = jax.lax.scan(body, vary_tree(x, va), store)
                if fn is not None:
                    # add the leading tp dim so caches match the global
                    # [tp, L, B, ...] convention
                    caches[g.name] = jax.tree.map(lambda t: t[None], ys)
            logits = model.head_logits(stem, x[:, -1:, :])
            return logits, caches

        return step

    def round_prefill_step_fn(self) -> Callable:
        """Batched prefill over one admission cohort: ``vmap`` of a
        per-sequence prefill pass over stacked prompt rows.

        ``tokens``: [K, S_prompt] int32.  Returns ``(first_tokens [K],
        caches)`` where every cache leaf is [tp, L, K, ...per-seq...] —
        lane-stacked single-sequence caches, NOT a batched cache.  The
        lane layout is what makes the round step arch-agnostic: archs
        whose caches don't lead with the batch dim (zamba's stacked
        per-unit mamba states) vmap exactly like dense attention, and a
        lane's math is bit-identical to a batch-1 eager prefill (MoE
        capacity, which depends on token count, sees one sequence)."""
        ctx, cdtype = self.ctx, dtype_of(self.cfg.compute_dtype)
        model = self.model

        def step(pstores, tokens):
            stem = self._gather_tree("stem", pstores["stem"][0], dtype=cdtype)

            def lane(row):
                batch = {"tokens": row[None, :]}
                x, extras = model.embed(stem, batch)
                caches = {}
                for g in model.groups():
                    x, extras = model.between_groups(
                        g.name, x, extras, stem, batch)
                    store = pstores[g.name][0]

                    def body(cx, layer_store, _g=g):
                        params = self._gather_tree(
                            _g.name, layer_store, dtype=cdtype)
                        y, cache = _g.prefill(params, cx, extras, ctx)
                        return y, cache
                    x, ys = jax.lax.scan(body, x, store)
                    caches[g.name] = ys
                logits = model.head_logits(stem, x[:, -1:, :])
                return greedy_token(logits, self.cfg.vocab_size, ctx), caches

            toks, caches = jax.vmap(lane, in_axes=0, out_axes=(0, 1))(tokens)
            # [K, 1] -> [K]; re-add the leading tp dim ([tp, L, K, ...])
            return toks[:, 0], jax.tree.map(lambda t: t[None], caches)

        return step

    def round_decode_step_fn(self) -> Callable:
        """One compiled continuous-batching decode step over padded
        active-sequence slots.

        ``tokens``: [S_slots, 1] int32, ``pos``: [S_slots] int32 (the
        position-vector decode signature: every slot advances from its
        own position in ONE call).  Cache leaves are [tp, L, S_slots,
        ...per-seq...].  Each slot is an independent ``vmap`` lane, so
        free/stale slots decode garbage that cannot leak into live lanes
        — the host simply ignores their tokens, and a re-bound slot's
        rows are fully overwritten by the next prefill scatter."""
        ctx, cdtype = self.ctx, dtype_of(self.cfg.compute_dtype)
        model = self.model

        def step(pstores, caches, tokens, pos):
            stem = self._gather_tree("stem", pstores["stem"][0], dtype=cdtype)

            def lane(lane_caches, token, p):
                x = model.embed_decode(stem, token[None], p, None)
                extras = model.decode_extras(stem, x)
                new_caches = {}
                for g in model.groups():
                    if g.decode is None:
                        continue
                    store = pstores[g.name][0]

                    def body(cx, inp, _g=g):
                        layer_store, layer_cache = inp
                        params = self._gather_tree(
                            _g.name, layer_store, dtype=cdtype)
                        y, c2 = _g.decode(params, cx, layer_cache, p,
                                          extras, ctx)
                        return y, c2
                    x, ys = jax.lax.scan(body, x, (store, lane_caches[g.name]))
                    new_caches[g.name] = ys
                logits = model.head_logits(stem, x)
                return greedy_token(logits, self.cfg.vocab_size, ctx), new_caches

            lane_in = jax.tree.map(lambda t: t[0], caches)  # strip tp dim
            toks, new_caches = jax.vmap(
                lane, in_axes=(1, 0, 0), out_axes=(0, 1))(lane_in, tokens, pos)
            return toks[:, 0], jax.tree.map(lambda t: t[None], new_caches)

        return step

    def decode_step_fn(self) -> Callable:
        ctx, cdtype = self.ctx, dtype_of(self.cfg.compute_dtype)
        model = self.model

        def step(pstores, caches, token, pos):
            stem = self._gather_tree("stem", pstores["stem"][0], dtype=cdtype)
            x = model.embed_decode(stem, token, pos, None)
            extras = model.decode_extras(stem, x)
            new_caches = {}
            for g in model.groups():
                if g.decode is None:
                    continue
                store = pstores[g.name][0]
                cache = jax.tree.map(lambda t: t[0], caches[g.name])  # strip tp dim

                # NOTE: scanning over (store, cache) double-buffers the
                # cache (xs in + ys out) in the XLA:CPU memory analysis;
                # on TPU, loop in/out buffer donation elides one copy —
                # see EXPERIMENTS.md §Dry-run "cache-adjusted fit".
                def body(cx, inp, _g=g):
                    layer_store, layer_cache = inp
                    params = self._gather_tree(_g.name, layer_store, dtype=cdtype)
                    y, c2 = _g.decode(params, cx, layer_cache, pos, extras, ctx)
                    return y, c2
                x, ys = jax.lax.scan(body, x, (store, cache))
                new_caches[g.name] = jax.tree.map(lambda t: t[None], ys)
            logits = model.head_logits(stem, x)
            next_tok = greedy_token(logits, self.cfg.vocab_size, ctx)
            return next_tok, new_caches

        return step
