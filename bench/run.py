#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload gpt2-1b.offload --seed 7 --seconds 10 --trace 0

Reads the cell from ``BENCHMARK.json`` at the root of the checkout and
runs it on the chips JAX finds (``bench/harness.py``).  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiled window.  The numbers compared with the
plain reference are printed beside their limits, as the last lines of
standard error and under ``compared`` in the result.  Exits non-zero, and
prints no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import harness
    except ImportError as e:
        print(f"bench: run from a checkout of the repository ({e})", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except (harness.NoChip, harness.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
