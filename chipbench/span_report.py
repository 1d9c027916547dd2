#!/usr/bin/env python3
"""Run one cell traced and account for the host with the program's own
layer spans: self time per layer and per dispatcher phase, and the share
of the longest idle gaps each span covers.

    python3 chipbench/span_report.py --workload moldyn-244.campaign \\
        --seed 7 --seconds 51 [--out chiprun_out/spans.json]
    python3 chipbench/span_report.py --record \\
        chipbench/tests/data/program_spans_trace.json

The first form is one traced run of the cell, exactly as ``run.py --trace
1`` makes it, except that in this process the trace reduction
(`trace_reduce.load` / `reduce`) is wrapped so that the reduced trace also
holds ``"program_spans"`` (`program_spans.reduce`); the run then reads,
besides the cell's own metrics, the span metrics (``metrics/
engine_us_per_task.py`` ...), the end-to-end metrics and the window's
counters.  It prints one JSON line; `--out` also writes it with every
span name's count, total and self seconds and the cover of the three
longest idle gaps.

``--record`` makes the small trace the reduction is tested on: a MolDyn
campaign of two molecules at the published widths (fused bundles) and
eight fMRI volumes one at a time (one-task bundles), each run once to
compile and once under the profiler; it writes the program's spans.

Needs the chip, like `run.py`; it is a tool for PERF.md, not part of a
benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SPAN_METRICS = {
    "batch": ["engine_us_per_task", "falkon_us_per_task", "pool_us_per_task",
              "dispatch_stack_share", "dispatch_fetch_share"],
    "stream": ["engine_us_per_task", "falkon_us_per_task",
               "pool_us_per_task", "singleton_ms"],
}
CLOCK_THREAD = ("engine.", "falkon.", "pool.submit", "pool.complete")
# what --record runs: (cell, its spec changed by, unit parameters, units
# one after another)
RECORDED = (("moldyn-244.campaign", {"molecules": 2}, {"chains": True}, 1),
            ("fmri-airsn.stream", {}, {"volumes": 1}, 8))


@contextlib.contextmanager
def _reduction_with_spans(captured: dict):
    """While open, this process's trace reduction keeps the program's
    spans (and `captured` gets them with the gaps' cover)."""
    from chipbench import program_spans, trace_reduce

    load, reduce = trace_reduce.load, trace_reduce.reduce

    def load_with_spans(path):
        trace = load(path)
        trace["spans"] = program_spans.load(path)
        return trace

    def reduce_with_spans(trace, top=10):
        out = reduce(trace, top)
        lo, hi = out["window"]
        spans = trace.get("spans", [])
        out["program_spans"] = program_spans.reduce(spans, lo, hi)
        captured["program_spans"] = out["program_spans"]
        captured["gap_cover"] = program_spans.gap_cover(trace, spans, lo, hi)
        return out

    trace_reduce.load = load_with_spans
    trace_reduce.reduce = reduce_with_spans
    try:
        yield
    finally:
        trace_reduce.load, trace_reduce.reduce = load, reduce


def report(cell, seed: int, seconds: float, device, count: int) -> dict:
    """One traced run of `cell` (a `harness.Cell`) with the span metrics
    read beside its own."""
    from chipbench import harness

    split = "stream" if cell.traffic["loop"] == "open" else "batch"
    extra = {f"{b}.{split}": harness.metric_reader(f"{b}.{split}")
             for b in SPAN_METRICS[split]}
    for m in cell.end_to_end:
        extra[m["name"]] = cell.readers[m["name"]]
    extra["tasks_run"] = lambda run: run.counters["tasks_run"]
    extra["dispatcher_s"] = lambda run: run.counters["dispatcher_s"]
    listed = {m["name"] for m in cell.per_layer}
    for name, fn in extra.items():
        if name not in listed:
            cell.per_layer.append({"name": name, "unit": ""})
            cell.readers[name] = fn
    captured: dict = {}
    with _reduction_with_spans(captured):
        res = harness.run_cell(cell, seed, seconds, True, T_START, device,
                               count)
    values = {k: v["value"] for k, v in res["metrics"].items()}
    ps = captured.get("program_spans", {})
    tasks = values.get("tasks_run") or 0
    window = res["device"].get("window_s")
    clock_spans = sum(r["count"] for n, r in ps.items()
                      if n.startswith(CLOCK_THREAD))
    return {
        "workload": cell.name, "seed": seed, "correct": res["correct"],
        "checks": res["checks"], "metrics": values,
        "device": res["device"],
        "clock_thread_spans_per_task": clock_spans / tasks if tasks else None,
        "self_share": {n: 100.0 * r["self_s"] / window
                       for n, r in sorted(ps.items())} if window else {},
        "spans": {n: {"count": r["count"], "total_s": r["total_s"],
                      "self_s": r["self_s"]} for n, r in sorted(ps.items())},
        "gap_cover": captured.get("gap_cover", []),
        "idle_gaps": res.get("breakdown", {}).get("idle_gaps", []),
    }


def record(path: str, what=RECORDED) -> None:
    """Write the program's spans of a short traced run (see the module
    docstring) to `path`, times from the trace's first span."""
    import glob
    import os
    import shutil
    import tempfile

    import jax

    from chipbench import harness, program_spans

    bench = harness.load_bench()
    runs = []
    for workload, spec, unit, units in what:
        cell = harness.Cell(bench, workload)
        cell.spec.update(spec)
        data = cell.config.make_data(cell.spec, 0)
        system = harness.System(cell.spec)

        def go(system=system, cell=cell, data=data, unit=unit, units=units):
            for i in range(units):
                cell.config.submit(system.wf, cell.spec, data, unit, i)
                system.clock.run()

        runs.append((system, go))
    for _system, go in runs:
        go()                                    # compiles
    trace_dir = tempfile.mkdtemp(prefix="chipbench-spans-")
    jax.profiler.start_trace(trace_dir)
    for _system, go in runs:
        go()
    jax.profiler.stop_trace()
    for system, _go in runs:
        system.shutdown()
    xplane = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)[0]
    spans = program_spans.load(xplane)
    shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = min(s for _l, _n, s, _d in spans)
    Path(path).write_text(json.dumps(
        {"spans": [[ln, n, s - t0, d] for ln, n, s, d in spans]},
        separators=(",", ":")))
    harness.info(f"recorded {len(spans)} spans to {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out")
    ap.add_argument("--record", metavar="PATH")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness

    if not (args.record or args.workload):
        ap.error("--workload or --record is needed")
    cell = harness.Cell(harness.load_bench(),
                        args.workload or RECORDED[0][0])
    device, count = harness.check_device(cell.chips)
    harness.configure_compile_cache()
    if args.record:
        record(args.record)
        return 0
    out = report(cell, args.seed, args.seconds, device, count)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    brief = {k: out[k] for k in ("workload", "seed", "correct", "metrics",
                                 "clock_thread_spans_per_task",
                                 "self_share", "gap_cover")}
    print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
