#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

  python3 bench/calibrate.py --workload gpt2-1b.offload --seed 7 --side program

On the chip, at the cell's own size, for one seed and one side (one
process each: the chip's host memory is not handed back between runs):

* ``program``: one run of the program as the benchmark runs it (a short
  window), giving each compared number: a lower reading;
* ``control``: the reference put in the program's place in lower
  precision (``train3(lower=True)``) against the reference: an upper
  reading;
* ``half_batch``: the reference on batches whose second half repeats the
  first (the fault of half the batch left out, planted in the reference)
  against the reference: an upper reading.

A step that returns its state unchanged reads 1 on ``change_gap`` by
construction and is not run.

Prints one JSON line per reading.  The benchmark's runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--side", choices=("program", "control", "half_batch"),
                    required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import compare, harness
    from bench.corpus import Corpus
    from bench.reference import train

    harness.use_compile_cache()
    seed = args.seed
    if args.side == "program":
        r = harness.run(args.workload, seed, 0.1, False, t_start=T_START)
        print(json.dumps({"side": "program", "seed": seed, "correct": r["correct"],
                          **{k: v["value"] for k, v in r["compared"].items()}}),
              flush=True)
        return
    cell = harness.load_cell(args.workload)
    fam = importlib.import_module(f"bench.reference.{cell.cfg['family']}")
    tr = cell.traffic
    corpus = Corpus(cell.cfg["vocab_size"], seed, **tr["corpus"])
    batches = [corpus.batch(tr["rows"], tr["seq"], k) for k in range(tr["checked_steps"])]
    run = lambda b, **kw: train.train3(fam, cell.cfg, tr["optimizer"], seed, b,
                                       memory_kind="pinned_host", **kw)
    want = run(batches)
    got = (run(batches, lower=True) if args.side == "control"
           else run([harness.half_batch(b) for b in batches]))
    numbers, _ = compare.gaps(got, want)
    print(json.dumps({"side": args.side, "seed": seed,
                      **{k: v[0] for k, v in numbers.items()}}), flush=True)


if __name__ == "__main__":
    main()
