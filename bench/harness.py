"""One run of one training cell: set-up, checked steps, window, check.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<file>.json``, whose ``family`` names
``bench/reference/<family>.py`` and ``bench/adapters/<family>.py``), a
traffic mix (``bench/traffic/<traffic>.json``: batch, sequence,
placement, optimizer, corpus) and its chips.  The limits of its
comparison are in ``bench/limits/<cell>.json``, and each per-layer metric
is read by ``bench/metrics/<metric>.py``.  Adding a cell, a configuration
or a metric adds files; nothing here names one.  The family describes
the configuration's shape: the keys its file must hold, its layer groups
in model order (which must be the program's), each group's layers and
weights, and the model FLOPs per token (``bench/reference/decoder.py``
lists the protocol); the adapter maps the file to the program's config
and hooks the weights into it.

A run:

1. builds the program's runtime for the cell, packs the benchmark's
   weights for ``--seed`` into its stores (``weights.init_state``) and
   compiles its train step (``driver.build_train_step``);
2. runs the first ``checked_steps`` (3) steps through that same compiled
   step, on batches that all differ, and reads the program's fp32 state
   after step 1 and step 3 (:func:`summarizer`);
3. measures for ``--seconds``: every further step on a fresh batch, at
   most two in flight, under the profiler with ``--trace 1``;
4. frees the program's state and runs the plain reference through the
   same three steps (``bench/reference/train.py``), and compares.

``setup_s`` is the time from process start to the window's start, less
the time spent reading the program's state for the check.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Any, Callable

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = ROOT / ".bench_runs"  # traces; listed in .gitignore

TRAFFIC_KEYS = {"rows", "seq", "dp", "os_host_fraction", "optimizer", "remat",
                "gather_policy", "corpus", "checked_steps"}
CONFIG_KEYS = {"family", "source"}  # and the keys the family lists as its own


class SpecError(ValueError):
    """A cell, configuration, traffic or limits file is missing or malformed."""


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _read_json(path: pathlib.Path, what: str) -> dict:
    try:
        data = json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{what}: {path} does not exist") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{what}: {path} is not JSON ({e})") from None
    if not isinstance(data, dict):
        raise SpecError(f"{what}: {path} does not hold a JSON object")
    return data


def _need(data: dict, keys: set, what: str) -> None:
    missing = sorted(keys - set(data))
    if missing:
        raise SpecError(f"{what} lacks {missing}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    cfg: dict
    traffic: dict
    limits: dict
    per_layer: list  # the per_layer entries of BENCHMARK.json this cell reports
    end_to_end: list
    fam: Any  # the reference family, bench/reference/<family>.py


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    spec_file = root / "BENCHMARK.json"
    spec = _read_json(spec_file, "benchmark")
    cells = [w for w in spec.get("workloads", []) if w.get("name") == name]
    if len(cells) != 1:
        raise SpecError(f"{spec_file} has {len(cells)} workloads named {name!r}")
    w = cells[0]
    _need(w, {"config", "traffic", "chips"}, f"workload {name}")
    confs = [c for c in spec.get("configs", []) if c.get("name") == w["config"]]
    if len(confs) != 1:
        raise SpecError(f"{spec_file} has {len(confs)} configs named {w['config']!r}")
    cfg = _read_json(root / confs[0]["file"], f"config {w['config']}")
    _need(cfg, CONFIG_KEYS, f"config {w['config']}")
    try:
        fam = importlib.import_module(f"bench.reference.{cfg['family']}")
    except ModuleNotFoundError as e:
        raise SpecError(f"config {w['config']}: no family {cfg['family']!r} ({e})") from None
    _need(cfg, fam.CONFIG_KEYS, f"config {w['config']}")
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']}")
    _need(traffic, TRAFFIC_KEYS, f"traffic {w['traffic']}")
    limits = _read_json(root / "bench" / "limits" / f"{name}.json", f"limits of {name}")
    if not limits or not all(isinstance(v, (int, float)) for v in limits.values()):
        raise SpecError(f"limits of {name} must map each number to a limit")
    if w["chips"] not in (1, 4) or traffic["dp"] > w["chips"]:
        raise SpecError(f"workload {name}: dp {traffic['dp']} on {w['chips']} chips")
    reports = lambda m: name in m.get("workloads", [name])
    return Cell(name, w["chips"], w["config"], cfg, traffic, limits,
                [m for m in spec.get("per_layer", []) if reports(m)],
                [m for m in spec.get("end_to_end", []) if reports(m)], fam)


def check_groups(rt, fam, cfg: dict) -> None:
    """The family's layer groups must be the program's, in name, length
    and order: leaves are named and weights keyed by them."""
    want, got = list(fam.groups(cfg)), list(rt.group_lengths.items())
    if [tuple(g) for g in want] != got:
        raise SpecError(f"the family's layer groups {want} are not the program's {got}")


def use_compile_cache() -> None:
    """The program's compile cache (``JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), holding every program, so that only a
    cell's first run compiles."""
    import jax

    from repro.launch.train import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ---------------------------------------------------------------------------
# reading the program's state
# ---------------------------------------------------------------------------


def summarizer(rt, fam, cfg: dict):
    """-> jitted ``f(pstores, osstores, weight key)`` giving, for every
    leaf of every layer and of the stem: the norms of p32 - p0, of m and
    of v, and the largest gap between the bf16
    working copy and bf16(p32).  p0 is the benchmark's initial weights,
    made again from the key by each group's own init on its own keys.
    Host-resident slices are brought to HBM one layer at a time."""
    import jax
    import jax.numpy as jnp

    from bench import weights
    from repro.core import zero

    f32 = jnp.float32

    # reduce_precision rounds to bf16 for certain: XLA may drop a pair of
    # casts f32 -> bf16 -> f32 as excess precision
    cast = lambda a: jax.lax.reduce_precision(a, 8, 7)

    def stats(lay, w, p32, m, v, p0):
        # all on the flat chunk vectors: a leaf is the range of positions
        # the layout gives it, picked out by a mask (slicing a leaf out of a
        # flat vector and reshaping it takes the TPU compiler minutes)
        # stores keep their [groups, ranks, chunk] shape: a reshape to one
        # axis copies them, which the stem of a large vocabulary cannot afford
        g, r, c = p32.shape
        iota = lambda d: jax.lax.broadcasted_iota(jnp.int32, p32.shape, d)
        idx = (iota(0) * r + iota(1)) * c + iota(2)
        ranges = [(lay.flat_offset(n), lay.flat_offset(n) + math.prod(s))
                  for n, s in zip(lay.names, lay.shapes)]
        # rounded again: XLA may drop the bf16 rounding of the initial
        # weights when the same program goes on in float32
        p0 = cast(zero.flatten_to_store(lay, p0).astype(f32))

        def norms(x):
            sq = jnp.square(x)
            return jnp.sqrt(jnp.stack([jnp.sum(jnp.where((idx >= lo) & (idx < hi), sq, 0))
                                       for lo, hi in ranges]))

        return {"dp": norms(p32 - p0), "m": norms(m), "v": norms(v),
                "cast": jnp.max(jnp.abs(w.astype(f32) - cast(p32)))}

    def state(ost, name, k, sl):
        parts = []
        for part, arr in ost[name][k].items():
            if arr.size == 0:
                continue
            x = sl(arr)
            if part == "host" and rt.host_memory_kind is not None:
                x = jax.device_put(x, jax.memory.Space.Device)
            parts.append(x)
        # the parts split the chunk groups: device groups first
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def summarize(pst, ost, wkey):
        out = {}
        for name, lay in rt.layouts.items():
            args = lambda sl: [sl(pst[name])] + [state(ost, name, k, sl)
                                                 for k in ("p32", "m", "v")]
            if name == "stem":
                drop = lambda a: a.reshape(a.shape[1:])
                out[name] = stats(lay, *args(drop),
                                  fam.init_stem(cfg, weights.stem_key(wkey)))
                continue
            n = pst[name].shape[1]
            keys = weights.group_keys(wkey, fam.groups(cfg))[name]

            def layer(i, _lay=lay, _args=args, _keys=keys, _name=name):
                sl = lambda a: jax.lax.dynamic_index_in_dim(a[0], i, 0, keepdims=False)
                return stats(_lay, *_args(sl), fam.init_layer(cfg, _keys[i], group=_name))
            out[name] = jax.lax.map(layer, jnp.arange(n))
        return out

    return jax.jit(summarize)


def _named(rt, raw) -> dict:
    """Device summary -> {stat: {leaf name: value}} with the reference's
    leaf names ("stem.x", "layers.3.x")."""
    import jax
    import numpy as np

    from bench.compare import leaf_names

    raw = jax.device_get(raw)
    out = {k: {} for k in ("dp", "m", "v")}
    cast = 0.0
    for name, lay in rt.layouts.items():
        tree = jax.tree_util.tree_unflatten(lay.treedef, [0] * len(lay.names))
        r = raw[name]
        prefixes = ["stem"] if name == "stem" else [
            f"{name}.{i}" for i in range(r["dp"].shape[0])]
        for i, prefix in enumerate(prefixes):
            names = leaf_names(tree, prefix)
            for k in out:
                vals = r[k] if name == "stem" else r[k][i]
                out[k].update(zip(names, map(float, vals)))
        cast = max(cast, float(np.max(r["cast"])))
    out["cast"] = cast
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunInfo:
    """What a per-layer metric's reader may read (``bench/metrics``)."""

    cell: Cell
    chips: int
    device_kind: str
    peak: dict  # bench/peaks.json entry of the device
    flops_per_token: float
    tokens_per_s: float  # of the traced window
    steps: int  # completed in the traced window
    window_s: float
    compiled: Any  # the timed executable
    trace: Any  # bench.trace.Trace of the window, or None


def half_batch(batch: dict) -> dict:
    """Fault: the second half of the rows replaced by the first half, so
    the mean is taken over the first half alone."""
    import numpy as np

    h = batch["tokens"].shape[0] // 2
    out = dict(batch)
    for k in ("tokens", "labels"):
        out[k] = np.concatenate([batch[k][:h], batch[k][:h], batch[k][2 * h:]])
    return out


def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        platforms: tuple = ("tpu",), fault: str | None = None,
        root: pathlib.Path = ROOT,
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True),
        ) -> dict:
    """Run cell ``name`` once and return the result line's object.

    ``fault`` breaks the timed path underneath, for the harness's tests:
    ``"frozen"`` (the step returns its state unchanged) or
    ``"half_batch"`` (the second half of each batch is left out)."""
    cell = load_cell(name, root)
    import jax

    devs = jax.devices()
    if devs[0].platform not in platforms:
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < cell.chips:
        raise NoChip(f"{name} needs {cell.chips} chips, JAX found {len(devs)}")
    peaks = _read_json(BENCH / "peaks.json", "peak table")
    kind = devs[0].device_kind
    if devs[0].platform == "tpu" and kind not in peaks:
        raise SpecError(f"{kind!r} is not in bench/peaks.json")

    import jax.numpy as jnp
    import numpy as np

    from bench import weights
    from bench.corpus import Corpus
    from bench.reference import train as ref_train
    from repro.configs import model_class
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_smoke_mesh
    from repro.runtime import driver
    from repro.runtime.step import ChunkedRuntime, RuntimeOptions

    fam = cell.fam
    adapter = importlib.import_module(f"bench.adapters.{cell.cfg['family']}")
    tr, opt = cell.traffic, cell.traffic["optimizer"]
    rows, seq, dp = tr["rows"], tr["seq"], tr["dp"]
    tokens_per_step = rows * seq

    pcfg = adapter.program_config(cell.config_name, cell.cfg)
    rt = ChunkedRuntime(model_class(pcfg), pcfg, make_smoke_mesh(dp, 1), RuntimeOptions(
        remat=tr["remat"], gather_policy=tr["gather_policy"],
        os_host_fraction=tr["os_host_fraction"], lr=opt["lr"],
        betas=tuple(opt["betas"]), eps=opt["eps"]))
    check_groups(rt, fam, cell.cfg)
    adapter.install_weights(rt, cell.cfg)
    phases = {"start": time.perf_counter() - t_start}
    pst, ost = jax.block_until_ready(weights.init_state(rt, seed))
    phases["init_state"] = time.perf_counter() - t_start
    jf, specs, in_sh = driver.build_train_step(rt, InputShape("bench", seq, rows, "train"))
    compiled = jf.lower(*specs).compile()
    phases["compile"] = time.perf_counter() - t_start
    step = compiled
    if fault == "frozen":
        def step(p, o, b, i):
            keep = jax.tree.map(jnp.copy, (p, o))
            return (*keep, compiled(p, o, b, i)[2])
    elif fault is not None and fault != "half_batch":
        raise ValueError(f"unknown fault {fault!r}")

    corpus = Corpus(pcfg.vocab_size, seed, **tr["corpus"])

    def feed(k):
        b = corpus.batch(rows, seq, k)
        b["global_tokens"] = np.float32(tokens_per_step)
        return jax.device_put(half_batch(b) if fault == "half_batch" else b, in_sh[2])

    idx = lambda k: jax.device_put(np.int32(k), in_sh[3])

    # checked steps: the first steps of the timed object, read back
    wkey = weights.weight_key(seed)
    summarize = summarizer(rt, fam, cell.cfg)
    check_s = 0.0

    def read_state():
        nonlocal check_s
        t = time.perf_counter()
        s = _named(rt, summarize(pst, ost, wkey))
        check_s += time.perf_counter() - t
        return s

    s0 = read_state()
    n_checked = tr["checked_steps"]
    losses, s1 = [], None
    for k in range(n_checked):
        pst, ost, met = step(pst, ost, feed(k), idx(k))
        losses.append(float(met["loss"]))
        if k == 0:
            s1 = read_state()
    s3 = read_state()

    # the window
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev) if "backend_compile" in ev else None)
    n_before = len(compiles)
    tdir = RUNS / "trace" / name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        jax.profiler.start_trace(str(tdir))
    k, done, window_losses, pending = n_checked, 0, [], None
    t_w0 = time.perf_counter()
    phases["checked_steps"] = t_w0 - t_start
    setup_s = t_w0 - t_start - check_s
    deadline = t_w0 + seconds
    finished = [t_w0]  # when each step's loss was seen, for the log
    # the host's spans, by which a trace names the device's idle gaps
    span = jax.profiler.TraceAnnotation
    while True:  # one step in flight while the next is fed
        with span("feed"):
            args = feed(k), idx(k)
        with span("dispatch"):
            pst, ost, met = step(pst, ost, *args)
        k += 1
        if pending is not None:
            with span("wait_loss"):
                window_losses.append(pending.block_until_ready())
            finished.append(time.perf_counter())
            done += 1
        pending = met["loss"]
        if time.perf_counter() >= deadline:
            break
    with span("wait_loss"):
        window_losses.append(pending.block_until_ready())
    done += 1
    t_end = time.perf_counter()
    finished.append(t_end)
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_w0
    n_compiles = len(compiles) - n_before
    tokens_per_s = done * tokens_per_step / window_s
    failed = sum(not math.isfinite(float(x)) for x in window_losses)
    failed += sum(not math.isfinite(x) for x in losses)

    used = devs[:dp]
    mem = compiled.memory_analysis()
    footprint = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    device = {"platform": devs[0].platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": int(max(in_use, footprint))}

    metrics = {}
    if trace:
        from bench import trace as trace_mod

        tr_data = trace_mod.load(tdir, [d.id for d in used])
        device["busy_s"] = tr_data.busy_s()
        device["window_s"] = window_s
        info = RunInfo(cell, len(used), kind, peaks.get(kind, {}),
                       fam.flops_per_token(cell.cfg, seq), tokens_per_s, done,
                       window_s, compiled, tr_data)
        for m in cell.per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = tr_data.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {"tokens_per_s": {"value": tokens_per_s, "unit": units["tokens_per_s"]},
                   "setup_s": {"value": setup_s, "unit": units["setup_s"]}}

    log(f"[{name}] seed={seed} setup_s={setup_s} check_read_s={check_s} "
        f"window_s={window_s} steps={done} tokens_per_s={tokens_per_s} "
        f"compiles_in_window={n_compiles} losses={losses}")
    between = [b - a for a, b in zip(finished[1:], finished[2:])] or [0.0]
    log(f"[{name}] set-up phases, seconds from process start: {phases}; "
        f"between steps in the window: shortest {min(between)} longest {max(between)}")
    log(f"[{name}] memory: peak_bytes_in_use={in_use} step_footprint={footprint} "
        f"(arguments {mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}, "
        f"aliased {mem.alias_size_in_bytes}, temporaries {mem.temp_size_in_bytes}, "
        f"host temporaries {getattr(mem, 'host_temp_size_in_bytes', 'n/a')})")

    # the reference, once the program's state is gone
    del pst, ost, met, pending, step, compiled, jf
    gc.collect()
    t = time.perf_counter()
    want = ref_train.train3(
        fam, cell.cfg, opt, seed, [corpus.batch(rows, seq, k) for k in range(n_checked)],
        memory_kind=None if devs[0].platform == "cpu" else "pinned_host",
        log=lambda s: log(f"[{name}] {s}"))
    ref_s = time.perf_counter() - t
    b1 = opt["betas"][0]
    got = {"loss": losses, "g1": {n: x / (1 - b1) for n, x in s1["m"].items()},
           "dp": s3["dp"], "m": s3["m"], "v": s3["v"], "cast": s3["cast"]}
    from bench import compare

    numbers, excluded = compare.gaps(got, want)
    numbers["init_gap"] = (max(max(s0[k].values()) for k in ("dp", "m", "v"))
                           + s0["cast"], "")
    ok, lines = compare.verdict(numbers, cell.limits)
    log(f"[{name}] reference_s={ref_s} loss={want['loss']} program_loss={losses}")
    log(f"[{name}] left out (reference gradient under {compare.NULL_GRAD} of the "
        f"median leaf's): {excluded}")
    for n, (v, at) in sorted(numbers.items()):
        if at:
            log(f"[{name}] {n} worst at {at}")
    for line in lines:
        log(line)

    result = {"correct": ok, "attempted": n_checked + done, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["compared"] = {n: {"value": v, "limit": cell.limits.get(n)}
                          for n, (v, _) in sorted(numbers.items())}
    return result
