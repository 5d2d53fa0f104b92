"""Benchmark suite runner: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines."""

# The comm-volume benchmark compiles a dp=2 x tp=2 step, so under
# JAX_PLATFORMS=cpu the bench process fakes 4 host devices (NOT the
# dry-run's 512 — that stays local to repro/launch/dryrun.py).
import os

from repro.launch.mesh import fake_cpu_devices

fake_cpu_devices(4)

import sys
import traceback

MODULES = [
    "benchmarks.model_scale",     # Fig. 13
    "benchmarks.throughput",      # Fig. 14/15
    "benchmarks.breakdown",       # Fig. 16
    "benchmarks.comm_volume",     # Sec. 7 / Table 5
    "benchmarks.chunk_search",    # Table 3 / Fig. 12
    "benchmarks.eviction",        # Sec. 8.3
    "benchmarks.tracer_bench",    # Fig. 2 / Sec. 8.1
    "benchmarks.max_batch",       # Sec. 6 "larger batch" / act stream
    "benchmarks.serving",         # serving plane: kv stream capacity
    "benchmarks.serving_compiled",  # compiled round-step scaling
    "benchmarks.timeline",        # transfer timeline / Fig. 16 stalls
    "benchmarks.serving_scale",   # paged KV + rank-sharded fleet capacity
    "benchmarks.tiers",           # third-tier (ZeRO-Infinity) host-wall unlock
    "benchmarks.cotenancy",       # multi-tenant pool: train + serve co-resident
]


def main() -> None:
    import argparse
    import importlib

    # Only --trace-dir is consumed here; everything else (e.g. --smoke)
    # stays on sys.argv for the per-module argparsers.
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--trace-dir", default=None)
    ns, rest = ap.parse_known_args()
    sys.argv = [sys.argv[0]] + rest

    if ns.trace_dir:
        os.makedirs(ns.trace_dir, exist_ok=True)
        from repro.analysis import tracereport
        from repro.core import telemetry

    print("name,us_per_call,derived")
    failures = 0
    for mod in MODULES:
        hub = None
        if ns.trace_dir:
            # Fresh hub per module, installed as the default so every
            # pool the module builds is traced with zero wiring; state
            # events off to keep CI traces lean.
            hub = telemetry.Telemetry(capture_states=False)
            telemetry.set_default_hub(hub)
        try:
            importlib.import_module(mod).main()
            if hub is not None and hub.events:
                path = os.path.join(ns.trace_dir,
                                    mod.rsplit(".", 1)[-1] + ".json")
                trace = hub.dump_chrome_trace(path)
                # re-load what we just wrote and re-assert conservation
                # both from the JSON and against the live counters
                tracereport.validate(tracereport.load(path))
                hub.assert_conservation()
                print(f"{mod},0.0,trace={path};"
                      f"events={len(hub.events)}")
                del trace
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{mod},0.0,ERROR")
        finally:
            if hub is not None:
                telemetry.set_default_hub(None)
    if failures:
        sys.exit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
