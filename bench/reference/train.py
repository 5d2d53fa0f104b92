"""Three plain Adam steps of a reference model, layer by layer.

The forward pass walks the family's layer groups in model order and keeps
each layer's input; the backward pass walks the layers in reverse, takes
each layer's gradient by ``jax.vjp`` and updates that layer at once, so
one layer's gradient is alive at a time.  A layer that returns
``(x, aux)`` adds ``aux`` to the objective: its cotangent is 1, as the
program differentiates its loss plus the layers' aux terms; the loss
reported is the language-model loss alone.  Leaves are named
``<group>.<i>.<path>`` and ``stem.<path>``.  The
output head runs over blocks of rows.  The fp32 master copy stays on the
device and Adam's moments, one layer at a time, in ``memory_kind``
(``pinned_host`` on a chip), so the whole state never has to fit in HBM
beside the activations.

Adam (Kingma and Ba, 2015) with bias correction and no weight decay:
m = b1 m + (1 - b1) g;  v = b2 v + (1 - b2) g^2;
p -= lr (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps).

The configuration states bf16 parameters with an fp32 master copy: the
forward and backward passes see the master copy rounded to bf16, Adam
updates the fp32 copy with the gradient taken there.  Activations and
gradients stay in float32.  The rounded copy is made by a program of its
own whose outputs are bf16 arrays, and so are the initial weights: XLA
may drop a rounding to bf16 inside the program that goes on to use the
value in float32 (on a TPU the initial weights then lay off the bf16
grid, and the reference's loss fell as if its weights were fp32).

``lower=True`` gives the control: every matrix product rounds its
operands to float8 (e4m3, scaled per tensor to its largest magnitude) in
the forward pass and passes gradients straight through, and the master
copy and the moments are rounded to bfloat16 after every update: the
precisions below the configuration's bf16 compute and fp32 state.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from bench import weights
from bench.compare import leaf_norms

HEAD_ROWS = 1024  # positions per block of the output head


def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    r = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    return x + jax.lax.stop_gradient(r - x)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def bf16(tree):
    """Round every leaf to the nearest bfloat16, kept as float32."""
    return jax.tree.map(lambda a: jax.lax.reduce_precision(a, 8, 7), tree)


_to_bf16 = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t))


def _adam(p, m, v, g, t, *, lr, b1, b2, eps, lower, host):
    if host:  # the moments live in host memory between steps
        m, v = jax.device_put((m, v), jax.memory.Space.Device)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
                     p, m, v)
    if lower:
        p, m, v = bf16(p), bf16(m), bf16(v)
    if host:
        m, v = jax.device_put((m, v), jax.memory.Space.Host)
    return p, m, v


def train3(fam, cfg: dict, job: dict, seed: int, batches: list[dict], *,
           lower: bool = False, memory_kind: str | None = None,
           log=lambda s: None) -> dict:
    """Run len(batches) steps from the benchmark's weights for ``seed``
    and return the summary :mod:`bench.compare` reads."""
    t0 = time.perf_counter()
    q = _fp8 if lower else (lambda t: t)
    groups = fam.groups(cfg)
    dev = jax.devices()[0]
    to_dev = functools.partial(jax.device_put, device=dev)
    with jax.default_matmul_precision("highest"):
        # the bf16 weights come out of a program of their own, so that
        # their rounding cannot be dropped before the cast to float32
        to_f32 = jax.jit(_f32)
        made = {g: jax.jit(functools.partial(fam.init_layer, cfg, group=g)) for g, _ in groups}
        init_layer = lambda g, k: to_f32(made[g](k))
        init_stem = lambda k, f=jax.jit(lambda k: fam.init_stem(cfg, k)): to_f32(f(k))

        # each pass sees the bf16 rounding of the master copy; the
        # gradient with respect to it is the gradient Adam applies
        def backward(p, x, dy, g):
            out, pull = jax.vjp(lambda p, x: fam.layer(cfg, p, x, q, group=g), _f32(p), x)
            if isinstance(out, tuple):  # (x, aux): aux enters the objective once
                return pull((dy, jnp.ones_like(out[1])))
            return pull(dy)

        fwd = {g: jax.jit(lambda p, x, g=g: fam.layer(cfg, _f32(p), x, q, group=g))
               for g, _ in groups}
        bwd = {g: jax.jit(functools.partial(backward, g=g)) for g, _ in groups}
        emb = jax.jit(lambda st, tok: fam.embed(cfg, _f32(st), tok))
        emb_bwd = jax.jit(lambda st, tok, dy: jax.vjp(
            lambda st: fam.embed(cfg, st, tok), _f32(st))[1](dy)[0])
        head = jax.jit(jax.value_and_grad(
            lambda st, x, lab: fam.head_loss_sum(cfg, st, x, lab, q),
            argnums=(0, 1)))
        host = memory_kind is not None
        adam = jax.jit(functools.partial(
            _adam, lr=job["lr"], b1=job["betas"][0], b2=job["betas"][1],
            eps=job["eps"], lower=lower, host=host))
        fetch = jax.jit(lambda t: jax.device_put(t, jax.memory.Space.Device)
                        if host else t)
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a))

        wkey = weights.weight_key(seed)
        keys = weights.group_keys(wkey, groups)
        # every layer of the model in order: (group, index in group, key)
        order = [(g, i, keys[g][i]) for g, n in groups for i in range(n)]
        stem = init_stem(weights.stem_key(wkey))
        layers = [init_layer(g, k) for g, _, k in order]
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        if host:
            zeros = jax.jit(lambda t: jax.device_put(
                jax.tree.map(jnp.zeros_like, t), jax.memory.Space.Host))
        mv = [[zeros(p), zeros(p)] for p in layers]
        mv_stem = [zeros(stem), zeros(stem)]
        out = {"loss": [], "g1": {}}

        for t, batch in enumerate(batches, start=1):
            tokens, labels = to_dev(batch["tokens"]), to_dev(batch["labels"])
            n_tok = tokens.size
            stem_b, layers_b = _to_bf16(stem), [_to_bf16(p) for p in layers]
            stem_h = to_f32(stem_b)  # the head's weights, as the passes see them
            xs = [emb(stem_b, tokens)]
            for (g, _, _), p in zip(order, layers_b):
                y = fwd[g](p, xs[-1])
                xs.append(y[0] if isinstance(y, tuple) else y)
            x = xs.pop()
            shape = x.shape
            x, lab = x.reshape(-1, shape[-1]), labels.reshape(-1)
            loss, g_stem, dxs = 0.0, None, []
            for i in range(0, n_tok, HEAD_ROWS):
                ls, (gs, dx) = head(stem_h, x[i:i + HEAD_ROWS], lab[i:i + HEAD_ROWS])
                loss += float(ls)
                g_stem = gs if g_stem is None else add(g_stem, gs)
                dxs.append(dx)
            out["loss"].append(loss / n_tok)
            dx = jnp.concatenate(dxs).reshape(shape) / n_tok
            del x, dxs
            for j in reversed(range(len(order))):
                g, i, _ = order[j]
                grad, dx = bwd[g](layers_b[j], xs.pop(), dx)
                if t == 1:
                    out["g1"].update(leaf_norms(grad, f"{g}.{i}"))
                layers[j], *mv[j] = adam(layers[j], *mv[j], grad, float(t))
                del grad
            # the head's gradient is of the sum over positions; dx is of the mean
            g_stem = add(scale(g_stem, 1.0 / n_tok), emb_bwd(stem_b, tokens, dx))
            if t == 1:
                out["g1"].update(leaf_norms(g_stem, "stem"))
            stem, *mv_stem = adam(stem, *mv_stem, g_stem, float(t))
            del g_stem, dx, stem_b, stem_h, layers_b
            log(f"reference step {t} at {time.perf_counter() - t0:.1f} s")

        diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
        out["dp"] = leaf_norms(diff(stem, init_stem(weights.stem_key(wkey))), "stem")
        out["m"] = leaf_norms(fetch(mv_stem[0]), "stem")
        out["v"] = leaf_norms(fetch(mv_stem[1]), "stem")
        for j, (g, i, k) in enumerate(order):
            out["dp"].update(leaf_norms(diff(layers[j], init_layer(g, k)), f"{g}.{i}"))
            out["m"].update(leaf_norms(fetch(mv[j][0]), f"{g}.{i}"))
            out["v"].update(leaf_norms(fetch(mv[j][1]), f"{g}.{i}"))
    log(f"reference done at {time.perf_counter() - t0:.1f} s")
    return out
