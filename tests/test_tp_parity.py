"""TP gradient parity: loss and every gradient at tp=2 must match the
tp=1 oracle (sharded grads concatenate; replicated grads psum-sync)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, model_class
from repro.core import zero
from repro.launch.mesh import make_smoke_mesh
from repro.models.layers import AxisCtx
from repro.runtime.step import ChunkedRuntime, RuntimeOptions

TP = 2

pytestmark = pytest.mark.slow  # per-arch grad sweeps: the sweeps CI job


def _split_tree(tree, ax_tree, rank, tp, shift=0):
    def split(p, ax):
        if ax is None:
            return p
        n = p.shape[ax + shift] // tp
        return jax.lax.slice_in_dim(p, rank * n, (rank + 1) * n, axis=ax + shift)
    return jax.tree.map(split, tree, ax_tree, is_leaf=lambda x: x is None)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mixtral-8x7b"])
def test_loss_and_grad_parity(arch):
    cfg = get_config(arch, smoke=True).replace(
        param_dtype="float32", compute_dtype="float32")
    B, S = 4, 32
    tok = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1),
             "global_tokens": jnp.float32(B * S)}

    # ---- tp=1 oracle: bare model, direct params --------------------------
    ctx1 = AxisCtx()
    model1 = model_class(cfg)(cfg, ctx1)
    params1 = model1.init_params(jax.random.key(7))

    def loss1(params):
        x, extras = model1.embed(params["stem"], batch)
        aux = jnp.float32(0.0)
        for g in model1.groups():
            x, extras = model1.between_groups(g.name, x, extras,
                                              params["stem"], batch)
            def body(c, lp, _g=g):
                cx, ca = c
                y, a = _g.apply(lp, cx, extras, ctx1)
                return (y, ca + jnp.float32(a)), None
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["groups"][g.name])
        return model1.head_loss(params["stem"], x, batch) + aux

    l1, g1 = jax.value_and_grad(loss1)(params1)

    # ---- tp=2 through the chunked runtime --------------------------------
    mesh = make_smoke_mesh(1, TP)
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh, RuntimeOptions())
    axes = rt.tp_axes

    def build_stores(rank):
        stem_l = _split_tree(params1["stem"], axes["stem"], rank, TP)
        st = {"stem": zero.flatten_to_store(rt.layouts["stem"], stem_l)[None]}
        for g in rt.model.groups():
            loc = _split_tree(params1["groups"][g.name],
                              axes["groups"][g.name], rank, TP, shift=1)
            arr = jax.vmap(lambda t, _l=rt.layouts[g.name]:
                           zero.flatten_to_store(_l, t))(loc)
            st[g.name] = arr[None]
        return st

    pstores = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                           *[build_stores(r) for r in range(TP)])

    def loss2(ps, batch):
        from repro.models.layers import vary_to
        tot = rt._loss_local(ps, batch)[0]
        # sum over data shards; model copies identical -> mean over model
        return jax.lax.psum(vary_to(tot, ("data", "model")),
                            ("data", "model")) / TP

    f = jax.jit(jax.shard_map(
        jax.value_and_grad(loss2), mesh=mesh,
        in_specs=(rt.store_pspecs(),
                  {"tokens": P(), "labels": P(), "global_tokens": P()}),
        out_specs=(P(), rt.store_pspecs()), check_vma=True))
    l2, g2 = f(pstores, batch)
    assert abs(float(l1) - float(l2)) < 5e-5 * max(1.0, abs(float(l1)))

    # ---- compare every gradient leaf --------------------------------------
    for g in rt.model.groups():
        lay = rt.layouts[g.name]
        parts = []
        for r in range(TP):
            flat = g2[g.name][r].reshape(g2[g.name][r].shape[0], -1)
            parts.append(jax.vmap(
                lambda f_, _l=lay: zero.unflatten_from_flat(_l, f_))(flat))
        ref = g1["groups"][g.name]
        ga = axes["groups"][g.name]
        flat_ref = jax.tree_util.tree_flatten_with_path(ref)[0]
        flat_ax = jax.tree.leaves(
            ga, is_leaf=lambda x: x is None or isinstance(x, int))
        flat_parts = [jax.tree_util.tree_flatten_with_path(t)[0] for t in parts]
        for i, ((path, a1), ax) in enumerate(zip(flat_ref, flat_ax)):
            ps = [fp[i][1] for fp in flat_parts]
            scale = float(jnp.max(jnp.abs(a1))) + 1e-9
            if ax is None:
                err = max(float(jnp.max(jnp.abs(p - a1))) for p in ps)
            else:
                cat = jnp.concatenate(ps, axis=ax + 1)
                err = float(jnp.max(jnp.abs(cat - a1)))
            assert err / scale < 2e-4, (
                f"{g.name}{jax.tree_util.keystr(path)}: relerr {err/scale:.2e}")
