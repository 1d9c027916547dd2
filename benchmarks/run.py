"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the paper claim it reproduces).  Detailed JSON lands in
benchmarks/results/.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

# allow `python benchmarks/run.py` from anywhere (not just -m with
# PYTHONPATH set): make both the repo root and src/ importable
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

MODULES = [
    "benchmarks.microbench",           # §4: 487 t/s, 54k executors, queue
    "benchmarks.efficiency",           # Fig 6
    "benchmarks.resource_efficiency",  # Fig 7
    "benchmarks.io_throughput",        # Fig 8
    "benchmarks.scalability",          # Fig 9
    "benchmarks.pipelining",           # Fig 10
    "benchmarks.load_balance",         # Fig 11
    "benchmarks.throughput",           # Fig 12
    "benchmarks.app_fmri",             # Fig 13
    "benchmarks.app_montage",          # Fig 14
    "benchmarks.app_moldyn",           # Fig 17/18
    "benchmarks.code_size",            # Table 1
    "benchmarks.device_batching",      # §11: device-batched executor pool
    "benchmarks.roofline",             # §Roofline (from dry-run artifacts)
    "benchmarks.million_tasks",        # scheduler scale (smoke-sized here)
    "benchmarks.data_diffusion",       # §6: cache-aware data layer
    "benchmarks.federation",           # §8: multi-engine federation
    "benchmarks.streaming_expansion",  # §9: windowed graph construction
    "benchmarks.real_throughput",      # §10: real threads, Fig-6 shape
    "benchmarks.observability",        # §12: tracing overhead + sample trace
    "benchmarks.health_recovery",      # §13: monitored recovery vs blind
    "benchmarks.real_federation",      # §14: process-per-shard dispatchers
    "benchmarks.kill_resume",          # §15: SIGKILL + resume re-run bound
]


def main() -> int:
    import argparse
    import importlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run "
                         "(e.g. --only microbench,million_tasks); "
                         "used by the CI smoke tier")
    args = ap.parse_args()
    modules = MODULES
    if args.only:
        wanted = {w.strip() for w in args.only.split(",") if w.strip()}
        modules = [m for m in MODULES if m.split(".")[-1] in wanted]
        missing = wanted - {m.split(".")[-1] for m in modules}
        if missing:
            sys.stderr.write(f"unknown benchmark modules: {missing}\n")
            return 2

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    for modname in modules:
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            rows = mod.run()
            for r in rows:
                derived = str(r["derived"]).replace(",", ";")
                print(f"{r['name']},{r['us_per_call']:.3f},{derived}",
                      flush=True)
        except Exception:
            failed += 1
            print(f"{modname},nan,FAILED", flush=True)
            traceback.print_exc(file=sys.stderr)
        sys.stderr.write(f"# {modname}: {time.time() - t0:.1f}s\n")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
