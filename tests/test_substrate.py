"""Data pipeline, checkpointing, roofline HLO parsing."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import parse_collectives
from repro.configs import get_config, model_class
from repro.configs.base import InputShape
from repro.data.pipeline import PackedLMLoader, SyntheticCorpus, make_batch_fn
from repro.launch.mesh import make_smoke_mesh
from repro.runtime import driver
from repro.runtime.step import ChunkedRuntime, RuntimeOptions


def test_corpus_is_deterministic_and_structured():
    c1 = SyntheticCorpus(512, seed=3)
    c2 = SyntheticCorpus(512, seed=3)
    t1, t2 = c1.tokens(4096), c2.tokens(4096)
    np.testing.assert_array_equal(t1, t2)
    assert t1.min() >= 0 and t1.max() < 512
    # motifs make the stream compressible: repeated 8-grams exist
    views = np.lib.stride_tricks.sliding_window_view(t1, 8)
    uniq = len({tuple(v) for v in views})
    assert uniq <= len(views) - 10  # injected motifs repeat


def test_loader_shards_disjoint_streams():
    c = SyntheticCorpus(128, seed=0)
    l0 = iter(PackedLMLoader(c, 2, 16, shard=(0, 2)))
    l1 = iter(PackedLMLoader(c, 2, 16, shard=(1, 2)))
    b0, b1 = next(l0), next(l1)
    assert b0["tokens"].shape == (2, 16)
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])


@pytest.mark.parametrize("arch", ["phi-3-vision-4.2b", "whisper-large-v3"])
def test_modality_batches(arch):
    cfg = get_config(arch, smoke=True)
    nxt = make_batch_fn(cfg, 2, 48)
    b = nxt()
    if cfg.arch_type == "vlm":
        assert b["patch_embeds"].shape == (2, cfg.num_patches, cfg.vision_dim)
        assert b["tokens"].shape == (2, 48 - cfg.num_patches)
    else:
        assert b["frames"].shape[0] == 2
        assert b["tokens"].shape == (2, 48)


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import checkpoint as ckpt

    cfg = get_config("qwen3-0.6b", smoke=True)
    mesh = make_smoke_mesh(2, 2)
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh, RuntimeOptions())
    ps, oss = driver.init_state(rt, jax.random.key(0))
    shape = InputShape("t", 32, 4, "train")
    step, _, _ = driver.build_train_step(rt, shape)
    tok = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1),
             "global_tokens": jnp.float32(128)}
    ps, oss, m0 = step(ps, oss, batch, jnp.int32(0))
    ckpt.save(rt, ps, oss, str(tmp_path / "ck"), step=1)

    rt2 = ChunkedRuntime(model_class(cfg), cfg, mesh, RuntimeOptions())
    ps2, oss2, step_no = ckpt.restore(rt2, str(tmp_path / "ck"))
    assert step_no == 1
    # resuming reproduces the same next step as continuing
    step2, _, _ = driver.build_train_step(rt2, shape)
    _, _, m_resume = step2(ps2, oss2, batch, jnp.int32(1))
    _, _, m_cont = step(ps, oss, batch, jnp.int32(1))
    assert abs(float(m_resume["loss"]) - float(m_cont["loss"])) < 1e-5


def test_parse_collectives_synthetic():
    hlo = """
  %ag = bf16[4,1408]{1,0} all-gather(bf16[1,1408]{1,0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[128]{0} all-reduce(f32[128]{0} %y), replica_groups=[2,2]<=[4], to_apply=%add
  %rs = f32[2,64]{1,0} reduce-scatter(f32[8,64]{1,0} %z), replica_groups={{0,1,2,3}}, dimensions={0}
  %dot = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
"""
    st = parse_collectives(hlo)
    assert set(st.by_kind) == {"all-gather", "all-reduce", "reduce-scatter"}
    ag = st.by_kind["all-gather"]
    assert ag[0] == 1 and ag[1] == 4 * 1408 * 2
    assert abs(ag[2] - 0.75 * 4 * 1408 * 2) < 1e-6
    ar = st.by_kind["all-reduce"]
    assert abs(ar[2] - 2 * 0.5 * 128 * 4) < 1e-6
    rs = st.by_kind["reduce-scatter"]
    assert abs(rs[2] - 0.75 * (2 * 64 * 4) * 4) < 1e-6


def test_train_hlo_has_chunked_collectives():
    """The compiled train step carries the paper's communication pattern:
    all-gather (chunk fetch) + reduce-scatter (grad release)."""
    cfg = get_config("qwen3-0.6b", smoke=True)
    mesh = make_smoke_mesh(2, 2)
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh, RuntimeOptions())
    shape = InputShape("t", 32, 4, "train")
    jf, args, _ = driver.build_train_step(rt, shape)
    txt = jf.lower(*args).compile().as_text()
    st = parse_collectives(txt)
    assert "all-gather" in st.by_kind
    assert "reduce-scatter" in st.by_kind


# ---------------------------------------------------------------------------
# host-resident optimizer state (os_host_fraction)
# ---------------------------------------------------------------------------


def _launch(*extra):
    from repro.launch import train as launcher

    args = launcher.parser().parse_args(
        ["--arch", "gpt2-paper-1b", "--smoke", "--dp", "2", "--batch", "4",
         "--seq", "32", "--steps", "3", "--repeat-batch", *extra])
    return launcher.train(args, log=lambda s: None)


@pytest.mark.parametrize("fraction", [0.5, 1.0])
def test_os_host_fraction_keeps_losses(fraction):
    """Where the optimizer-state groups live changes no arithmetic: the
    launcher's losses at fractions 0.5 and 1.0 equal those at 0.0."""
    want = _launch("--os-host-fraction", "0.0").losses
    got = _launch("--os-host-fraction", str(fraction)).losses
    assert len(got) == 3 and got == want
    assert got[-1] < got[0]


def test_profile_writes_step_and_feed_spans(tmp_path):
    """``--profile DIR`` leaves a profiler trace of steps 2 to 4 whose host
    spans name each step (``train``) and its batch transfer (``feed``)."""
    _launch("--steps", "4", "--profile", str(tmp_path))
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(str(files[0]))
    steps, names = set(), set()
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    names.add(ev.name)
                    if ev.name == "train":
                        steps.add(dict(ev.stats)["step_num"])
    assert {"train", "feed"} <= names
    assert steps == {2, 3}


@pytest.mark.parametrize("fraction", [0.0, 0.5, 1.0])
def test_init_state_matches_unsplit_init(fraction):
    """init_state packs ``model.init_params`` into the param stores bit for
    bit and starts the OS stores at (p32 = params, m = v = 0), however the
    OS groups are split between device and host."""
    cfg = get_config("gpt2-paper-1b", smoke=True)
    rt = ChunkedRuntime(model_class(cfg), cfg, make_smoke_mesh(1, 1),
                        RuntimeOptions(os_host_fraction=fraction))
    key = jax.random.key(0)
    ps, oss = driver.init_state(rt, key)

    from repro.core import zero

    @jax.jit
    def unsplit(key):
        # tp=1: sharded leaves draw from fold_in(key, rank 0), replicated
        # leaves from key itself
        ranked = rt.model.init_params(jax.random.fold_in(key, 0))
        shared = rt.model.init_params(key)
        pick = lambda axes, a, b: jax.tree.map(
            lambda ax, x, y: y if ax is None else x, axes, a, b,
            is_leaf=lambda x: x is None)
        out = {"stem": zero.flatten_to_store(rt.layouts["stem"], pick(
            rt.tp_axes["stem"], ranked["stem"], shared["stem"]))[None]}
        for g in rt.model.groups():
            tree = pick(rt.tp_axes["groups"][g.name],
                        ranked["groups"][g.name], shared["groups"][g.name])
            out[g.name] = jax.vmap(lambda t, _l=rt.layouts[g.name]:
                                   zero.flatten_to_store(_l, t))(tree)[None]
        return out

    for name, want in unsplit(key).items():
        gax = 1 if name == "stem" else 2
        assert ps[name].dtype == want.dtype
        assert bool(jnp.array_equal(ps[name], want)), name
        p32 = want.astype(jnp.float32)
        for k, ref in (("p32", p32), ("m", jnp.zeros_like(p32)),
                       ("v", jnp.zeros_like(p32))):
            dev, host = oss[name][k]["dev"], oss[name][k]["host"]
            assert (dev.shape[gax], host.shape[gax]) == rt.os_split(name)
            got = jnp.concatenate([dev, host], axis=gax)
            assert bool(jnp.array_equal(got, ref)), (name, k)


def test_host_placement_follows_mesh_platform():
    """The runtime reads the platform from its own mesh, not from
    ``jax.devices()`` (the CPU here): a TPU mesh puts host-resident OS
    groups in pinned_host."""
    from types import SimpleNamespace

    cfg = get_config("gpt2-paper-1b", smoke=True)
    mesh = SimpleNamespace(
        devices=np.array([[SimpleNamespace(platform="tpu")]]),
        axis_names=("data", "model"), shape={"data": 1, "model": 1})
    rt = ChunkedRuntime(model_class(cfg), cfg, mesh,
                        RuntimeOptions(os_host_fraction=1.0))
    assert rt.host_memory_kind == "pinned_host"


def test_cpu_mesh_keeps_host_groups_on_device():
    """Only an XLA:CPU mesh keeps host-resident OS groups in device memory."""
    cfg = get_config("gpt2-paper-1b", smoke=True)
    rt = ChunkedRuntime(model_class(cfg), cfg, make_smoke_mesh(1, 1),
                        RuntimeOptions(os_host_fraction=1.0))
    assert rt.host_memory_kind is None
    kinds = {sh["host"].memory_kind
             for st in driver.os_shardings(rt).values() for sh in st.values()}
    assert kinds == {"device"}
    _, oss = driver.init_state(rt, jax.random.key(0))
    assert {oss[n][k]["host"].sharding.memory_kind
            for n in oss for k in oss[n]} == {"device"}
