"""A model of layers of more than one kind, as a family describes it: the
reference's three steps walk its groups against an oracle, the keys of
its layers are distinct, and a two-group cell with an untied head runs
through the whole harness on the CPU."""

import json
import math
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness, weights
from bench.reference import decoder, train
from bench.tests import tiny, tiny_moe

# ---------------------------------------------------------------------------
# a family of two kinds of layer, the second with a term of the objective
# ---------------------------------------------------------------------------

D, F, V = 16, 24, 64
AUX = 0.25  # weight of kind b's term


def _normal(key, shape):
    return (jax.random.normal(key, shape) / math.sqrt(shape[0])).astype(jnp.bfloat16)


def _init_stem(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"embed": _normal(k1, (V, D)), "unembed": _normal(k2, (V, D))}


def _init_layer(cfg, key, *, group):
    k1, k2 = jax.random.split(key)
    if group == "a":
        return {"w1": _normal(k1, (D, F)), "w2": _normal(k2, (F, D))}
    return {"w": _normal(k1, (D, D)), "s": 1 + 0.1 * _normal(k2, (D,))}


def _layer(cfg, p, x, q=lambda t: t, *, group):
    if group == "a":
        return x + jnp.tanh(q(x) @ q(p["w1"])) @ q(p["w2"])
    h = q(x) @ q(p["w"])
    return x + jax.nn.gelu(h) * p["s"], AUX * jnp.mean(jnp.square(h))


def _head_loss_sum(cfg, stem, x, labels, q=lambda t: t):
    logits = q(x) @ q(stem["unembed"]).T
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


TWO_KINDS = types.SimpleNamespace(
    groups=lambda cfg: [("a", 1), ("b", 2)], init_stem=_init_stem,
    init_layer=_init_layer, layer=_layer,
    embed=lambda cfg, stem, tokens: jnp.take(stem["embed"], tokens, axis=0),
    head_loss_sum=_head_loss_sum)


def oracle(seed, batch, aux_weight=1.0):
    """Step 1's loss and gradient of the whole model at once, on the same
    bf16 weights: (language-model loss, {leaf name: gradient norm}), the
    aux terms in the objective at ``aux_weight``."""
    wkey = weights.weight_key(seed)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    keys = weights.group_keys(wkey, TWO_KINDS.groups({}))
    params = {"stem": f32(_init_stem({}, weights.stem_key(wkey))),
              "a": [f32(_init_layer({}, k, group="a")) for k in keys["a"]],
              "b": [f32(_init_layer({}, k, group="b")) for k in keys["b"]]}
    tokens, labels = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])

    def objective(params):
        x = jnp.take(params["stem"]["embed"], tokens, axis=0)
        aux = 0.0
        for p in params["a"]:
            x = _layer({}, p, x, group="a")
        for p in params["b"]:
            x, a = _layer({}, p, x, group="b")
            aux += a
        loss = _head_loss_sum({}, params["stem"], x.reshape(-1, D),
                              labels.reshape(-1)) / tokens.size
        return loss + aux_weight * aux, loss

    with jax.default_matmul_precision("highest"):
        (_, loss), g = jax.value_and_grad(objective, has_aux=True)(params)
    norms = compare.leaf_norms(g["stem"], "stem")
    for group in ("a", "b"):
        for i, p in enumerate(g[group]):
            norms.update(compare.leaf_norms(p, f"{group}.{i}"))
    return float(loss), norms


def test_train3_walks_two_groups_as_the_oracle_does():
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, V, (2, 8)).astype(np.int32),
             "labels": rng.integers(0, V, (2, 8)).astype(np.int32)}
    job = {"lr": 1e-3, "betas": [0.9, 0.95], "eps": 1e-8}
    got = train.train3(TWO_KINDS, {}, job, 11, [batch])
    loss, norms = oracle(11, batch)
    assert sorted(got["g1"]) == sorted(norms) == [
        "a.0.w1", "a.0.w2", "b.0.s", "b.0.w", "b.1.s", "b.1.w",
        "stem.embed", "stem.unembed"]
    assert got["loss"][0] == pytest.approx(loss, rel=1e-5)
    for name, want in norms.items():
        assert got["g1"][name] == pytest.approx(want, rel=1e-5), name
    # the aux terms are in it: without them kind b's gradients read otherwise
    _, plain = oracle(11, batch, aux_weight=0.0)
    for name in ("b.0.w", "b.1.w"):
        assert abs(plain[name] - norms[name]) > 1e-3 * norms[name], name


def test_no_two_layers_draw_the_same_key():
    wkey = weights.weight_key(2**33 + 5)
    keys = weights.group_keys(wkey, [("a", 1), ("b", 2), ("c", 3)])
    data = np.concatenate([np.asarray(jax.random.key_data(keys[g])) for g in "abc"])
    assert data.shape[0] == 6
    assert len({tuple(row) for row in data}) == 6
    # the model's layer l, over the groups in order, draws layer_keys(...)[l]
    np.testing.assert_array_equal(data, jax.random.key_data(weights.layer_keys(wkey, 6)))


@pytest.mark.parametrize("name", sorted(tiny.TINY))
def test_one_group_draws_the_layer_keys(name):
    cfg = tiny.TINY[name]
    wkey = weights.weight_key(2**40 + 3)
    (group, n), = decoder.groups(cfg)
    assert group == "layers"
    np.testing.assert_array_equal(
        jax.random.key_data(weights.group_keys(wkey, decoder.groups(cfg))["layers"]),
        jax.random.key_data(weights.layer_keys(wkey, n)))


# ---------------------------------------------------------------------------
# a dense-then-MoE cell with an untied head through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """The tiny root with the cell ``moe.offload``, its family registered."""
    root = tiny.make_root(tmp_path_factory.mktemp("moe"))
    (root / "bench" / "configs" / "moe.json").write_text(json.dumps(tiny_moe.CONFIG))
    (root / "bench" / "limits" / "moe.offload.json").write_text(json.dumps(tiny.LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "moe", "file": "bench/configs/moe.json"})
    spec["workloads"].append({"name": "moe.offload", "config": "moe",
                              "traffic": "offload", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "bench.reference.tiny_moe", tiny_moe)
        mp.setitem(sys.modules, "bench.adapters.tiny_moe", tiny_moe)
        yield root


def run_moe(root, **kw):
    return harness.run("moe.offload", 2**33 + 9, 0.3, False, t_start=time.perf_counter(),
                       platforms=("cpu",), root=root, **kw)


@pytest.mark.parametrize("fault", [None, "frozen", "half_batch"])
def test_two_group_cell_is_correct_only_without_a_fault(moe_root, fault):
    r = run_moe(moe_root, fault=fault)
    assert r["correct"] is (fault is None), r["compared"]
    assert r["failed"] == 0
    if fault is None:
        assert r["compared"]["init_gap"]["value"] == 0
        assert r["compared"]["cast_gap"]["value"] == 0


def test_the_program_groups_are_the_family_groups(moe_root):
    cell = harness.load_cell("moe.offload", moe_root)
    from repro.configs import model_class
    from repro.launch.mesh import make_smoke_mesh
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    pcfg = tiny_moe.program_config("moe", cell.cfg)
    rt = ChunkedRuntime(model_class(pcfg), pcfg, make_smoke_mesh(1, 1), RuntimeOptions())
    assert list(rt.group_lengths.items()) == tiny_moe.groups(cell.cfg) == [
        ("dense_layers", 1), ("moe_layers", 2)]


@pytest.mark.parametrize("groups", [
    [("layers", 3)], [("dense_layers", 2), ("moe_layers", 1)],
    [("moe_layers", 2), ("dense_layers", 1)], [("moe_layers", 3)]])
def test_groups_unlike_the_program_are_refused_before_compiling(moe_root, monkeypatch,
                                                                 groups):
    from repro.runtime import driver

    def never(*a, **kw):
        raise AssertionError("compiled or initialised with the groups unchecked")

    monkeypatch.setattr(tiny_moe, "groups", lambda cfg: groups)
    monkeypatch.setattr(driver, "init_state", never)
    monkeypatch.setattr(driver, "build_train_step", never)
    with pytest.raises(harness.SpecError, match="layer groups"):
        run_moe(moe_root)


@pytest.mark.parametrize("breakage", ["lacks_own_key", "no_such_family"])
def test_the_family_decides_the_keys(moe_root, breakage):
    f = moe_root / "bench" / "configs" / "moe.json"
    cfg = dict(tiny_moe.CONFIG)
    if breakage == "lacks_own_key":
        del cfg["n_routed_experts"]
    else:
        cfg["family"] = "no_such_family"
    f.write_text(json.dumps(cfg))
    try:
        with pytest.raises(harness.SpecError):
            harness.load_cell("moe.offload", moe_root)
    finally:
        f.write_text(json.dumps(tiny_moe.CONFIG))
