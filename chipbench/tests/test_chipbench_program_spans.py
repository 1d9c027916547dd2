"""The program's layer spans on the profiler's clock and their reduction:
a tiny Workflow -> Engine -> Falkon -> DeviceExecutorPool run profiled on
the CPU, a hand-made trace, a small trace recorded on a TPU v5e (a MolDyn
campaign of two molecules in fused bundles and eight fMRI volumes as
one-task bundles), the span metrics' readers, and the cell
``fmri-airsn.campaign`` from its own traffic file at a tiny volume size."""
import glob
import json
import os
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import (harness, program_spans, span_report,  # noqa: E402
                       trace_reduce)

RECORDED = pathlib.Path(__file__).with_name("data") / \
    "program_spans_trace.json"
CLOCK = {"engine.submit", "engine.ready", "engine.dispatch", "engine.done",
         "falkon.dispatch", "falkon.pump", "falkon.complete", "pool.submit",
         "pool.complete"}
DISPATCHER = {"pool.bundle", "bundle.stack", "bundle.h2d", "bundle.call",
              "bundle.wait", "bundle.d2h", "bundle.split", "bundle.single"}
PHASES = ("bundle.stack", "bundle.h2d", "bundle.call", "bundle.wait",
          "bundle.d2h", "bundle.split")


class FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1 << 20}


def step(x, w):
    return jax.numpy.tanh(x @ w) + x


def branchy(x, w):
    # Python control flow on a value: refuses jit(vmap), runs per task
    return x @ w if float(x.sum()) > 0 else -x


def join(*xs):
    return sum(xs)


def profiled_run(tmp_path, case: str):
    """A 3-stage pipeline over 12 inputs and a one-task join, under the
    profiler.  ``benchmark``: the benchmark's shape (executors to spare,
    one site); ``throttled``: 4 executors and the engine holding ready
    tasks beyond its site's throttle, so drains and pumps take place;
    ``fallback``: a body that refuses jit(vmap)."""
    from repro.core import (DRPConfig, DeviceExecutorPool, Engine,
                            FalkonConfig, FalkonProvider, FalkonService,
                            RealClock, Workflow)

    executors = 4 if case == "throttled" else 64
    clock = RealClock()
    pool = DeviceExecutorPool(clock, max_bundle=16)
    svc = FalkonService(clock, FalkonConfig(drp=DRPConfig(
        min_executors=executors, max_executors=executors,
        alloc_latency=0.0, alloc_chunk=executors)), pool=pool)
    svc.provision(executors)
    eng = Engine(clock, provenance="summary")
    if case == "throttled":
        # the engine holds ready tasks beyond 8 outstanding (its drain
        # places them), Falkon queues those beyond 4 executors (its pump
        # dispatches them)
        eng.add_site("dev", FalkonProvider(svc), capacity=2 * executors)
        eng.fair_share = True
        eng.site_slack = 1.0
    else:
        eng.add_site("dev", FalkonProvider(svc), capacity=executors)
    wf = Workflow("spans", eng)
    body = branchy if case == "fallback" else step
    stage = wf.atomic(body, name="step", vmap_key="step")
    fin = wf.atomic(join, name="join")
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8, 8)).astype(np.float32) / 8
    xs = [rng.normal(size=(4, 8)).astype(np.float32) for _ in range(12)]
    trace_dir = tmp_path / "trace"
    with jax.profiler.trace(str(trace_dir)):
        outs = [stage(stage(stage(x, w), w), w) for x in xs]
        total = fin(*outs)
        wf.run()
    svc.shutdown()
    assert total.resolved and not total.failed
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return program_spans.load(path), pool


def by_line(spans):
    lines: dict = {}
    for i, sp in enumerate(spans):
        lines.setdefault(sp[0], []).append(i)
    return lines


@pytest.mark.parametrize("case", ["benchmark", "throttled", "fallback"])
def test_profiled_run_records_every_layer_span(tmp_path, case):
    spans, pool = profiled_run(tmp_path, case)
    names = {sp[1] for sp in spans}
    want = CLOCK | DISPATCHER
    if case != "throttled":
        # executors to spare and one site: nothing waits, so no drain and
        # no pump with work; the throttled case makes both happen
        want -= {"engine.dispatch", "falkon.pump"}
    if case == "fallback":
        want = want - set(PHASES[2:]) | {"bundle.fallback"}
    assert want <= names, sorted(want - names)
    if case != "fallback":
        assert "bundle.fallback" not in names
    # the clock thread's spans and the dispatcher's lie on two lines
    lines = by_line(spans)
    own = {ln: {spans[i][1] for i in ix} for ln, ix in lines.items()}
    clock_lines = [ln for ln, n in own.items() if n & CLOCK]
    assert len(clock_lines) == 1 and not own[clock_lines[0]] & DISPATCHER
    # every bundle phase lies inside a pool.bundle of its own line
    for ln, ix in lines.items():
        bundles = [(spans[i][2], spans[i][2] + spans[i][3]) for i in ix
                   if spans[i][1] == "pool.bundle"]
        for i in ix:
            _l, name, s, d = spans[i]
            if name.startswith("bundle."):
                assert any(b0 <= s and s + d <= b1 for b0, b1 in bundles), \
                    name
    # self times of one line sum to no more than the union of its spans
    own_s = program_spans.self_seconds(spans)
    for ln, ix in lines.items():
        union = sum(e - s for s, e in trace_reduce._union(
            [(spans[i][2], spans[i][3]) for i in ix], -1e300, 1e300))
        assert sum(own_s[i] for i in ix) <= union * (1 + 1e-9) + 1e-9
    # the budget: at most 8 clock-thread spans per task
    clock_spans = sum(1 for sp in spans if sp[1] in CLOCK)
    assert pool.tasks_run == 12 * 3 + 1
    assert clock_spans / pool.tasks_run <= 8


def test_self_time_is_per_thread_line():
    spans = [
        # line a: A [0, 10] holds B [1, 4] (which holds C [2, 3]) and
        # D [5, 6]
        ["a", "pool.complete", 0.0, 10.0],
        ["a", "falkon.complete", 1.0, 3.0],
        ["a", "engine.done", 2.0, 1.0],
        ["a", "falkon.complete", 5.0, 1.0],
        # line b overlaps line a in time but nests nothing of it
        ["b", "pool.bundle", 0.5, 8.0],
        ["b", "bundle.call", 1.0, 2.0],
        ["b", "bundle.d2h", 7.0, 2.0],      # runs past its parent's end
    ]
    own = program_spans.self_seconds(spans)
    assert own == pytest.approx([10 - 3 - 1, 3 - 1, 1, 1,
                                 8 - 2 - 1.5, 2, 2])
    r = program_spans.reduce(spans, 0.0, 5.0)
    assert set(r) == {"pool.complete", "falkon.complete", "engine.done",
                      "pool.bundle", "bundle.call"}
    # the span at 5.0 starts outside [0, 5): one falkon.complete counted
    assert r["falkon.complete"] == {"count": 1, "total_s": 3.0,
                                    "self_s": pytest.approx(2.0),
                                    "durations": [3.0]}
    assert r["pool.complete"]["self_s"] == pytest.approx(6.0)


def test_gap_cover_names_the_spans_that_hold_the_host_through_a_gap():
    # device busy [1, 2] and [6, 7] in the window [1, 12]: gaps [7, 12]
    # and [2, 6], longest first
    trace = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["a", 1.0, 1.0], ["b", 6.0, 1.0]]}}}
    spans = [["c", "pool.bundle", 2.5, 3.0],
             ["c", "bundle.h2d", 3.0, 2.0],
             ["c", "bundle.h2d", 4.5, 1.0],     # overlaps the one before
             ["m", "engine.done", 8.0, 1.0]]
    gaps = program_spans.gap_cover(trace, spans, 1.0, 12.0, top=2)
    assert [(g["at_s"], g["seconds"]) for g in gaps] == [(6.0, 5.0),
                                                        (1.0, 4.0)]
    assert gaps[0]["cover"] == {"engine.done": 0.2,
                                "any program span": 0.2}
    assert gaps[1]["cover"] == pytest.approx(
        {"pool.bundle": 0.75, "bundle.h2d": 0.625,
         "any program span": 0.75})
    assert program_spans.gap_cover({"devices": {}}, spans, 1.0, 11.0) == []


def test_recorded_chip_trace_phases_cover_the_bundles():
    spans = json.loads(RECORDED.read_text())["spans"]
    names = {sp[1] for sp in spans}
    assert (CLOCK - {"engine.dispatch", "falkon.pump"}) | DISPATCHER \
        <= names
    own = program_spans.self_seconds(spans)
    fused = phases = 0.0
    for ln, ix in by_line(spans).items():
        for b in ix:
            _l, name, s, d = spans[b]
            if name != "pool.bundle":
                continue
            inside = [i for i in ix if s <= spans[i][2] <= s + d
                      and spans[i][1] in PHASES]
            if any(spans[i][1] == "bundle.call" for i in inside):
                fused += d
                phases += sum(own[i] for i in inside)
    assert fused > 0
    assert phases >= 0.95 * fused
    singles = [d for _l, n, _s, d in spans if n == "bundle.single"]
    assert len(singles) >= 8 * 4


def _run(trace=None, spans=None, tasks_run=100):
    bench = harness.load_bench()
    run = harness.Run(harness.Cell(bench, "fmri-airsn.stream"),
                      "TPU v5 lite")
    run.trace, run.spans = trace, spans
    run.counters = {"tasks_run": tasks_run}
    return run


class _Span:
    def __init__(self, started, dequeued):
        self.started, self.dequeued = started, dequeued


SPAN_READERS = {
    # reader: value on the hand-made trace below
    "engine_us_per_task.batch": 1e6 * (0.03 + 0.01) / 100,
    "falkon_us_per_task.stream": 1e6 * 0.02 / 100,
    "pool_us_per_task.batch": 1e6 * (0.005 + 0.004) / 100,
    "dispatch_stack_share.batch": 100.0 * (0.5 + 0.25) / 10.0,
    "dispatch_fetch_share.batch": 100.0 * (0.1 + 0.2 + 0.05) / 10.0,
    "singleton_ms.stream": 1000.0 * 0.006,
}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_read_the_program_spans(name):
    read = harness.metric_reader(name)
    # no trace, a trace without the program's spans (PR 12's reduction),
    # and an empty reduction all read nothing
    assert read(_run()) is None
    assert read(_run(trace={"window_s": 10.0})) is None
    assert read(_run(trace={"window_s": 10.0, "program_spans": {}})) is None

    def r(self_s, durations=()):
        return {"count": len(durations), "total_s": self_s,
                "self_s": self_s, "durations": list(durations)}

    ps = {"engine.submit": r(0.03), "engine.done": r(0.01),
          "falkon.dispatch": r(0.02), "pool.submit": r(0.005),
          "pool.complete": r(0.004), "pool.bundle": r(1.0),
          "bundle.stack": r(0.5), "bundle.h2d": r(0.25),
          "bundle.wait": r(0.1), "bundle.d2h": r(0.2),
          "bundle.split": r(0.05),
          "bundle.single": r(0.02, [0.004, 0.006, 0.01])}
    got = read(_run(trace={"window_s": 10.0, "program_spans": ps}))
    assert got == pytest.approx(SPAN_READERS[name])


def test_pool_queue_reader_reads_dequeued():
    read = harness.metric_reader("pool_queue_ms.stream")
    assert read(_run()) is None
    # spans of a program without the slot (the parent's) read nothing
    assert read(_run(spans=[object(), object()])) is None
    spans = [_Span(1.0, 1.002), _Span(2.0, 2.010), _Span(3.0, 3.004)]
    assert read(_run(spans=spans)) == pytest.approx(4.0)


def tiny(name, **spec):
    cell = harness.Cell(harness.load_bench(), name)
    cell.spec.update(spec)
    return cell


FMRI_TINY = dict(volume_shape=[8, 8, 4])


@pytest.mark.parametrize("trace", [False, True])
def test_fmri_campaign_cell_runs_from_its_own_traffic_file(trace):
    """`fmri-airsn.campaign` as BENCHMARK.json has it (``study-480``: 480
    volumes a unit, closed loop, two warm-up units), the volumes shrunk
    to 8x8x4."""
    cell = tiny("fmri-airsn.campaign", **FMRI_TINY)
    assert cell.traffic == {"loop": "closed", "unit": {"volumes": 480},
                            "clients": 1, "warm_up": {"units": 2}}
    res = harness.run_cell(cell, 2**31 + 21, 1.0, trace,
                           time.perf_counter(), FakeTpu(), 1)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1920
    listed = {m["name"] for m in harness.load_bench()["per_layer"]
              if "fmri-airsn.campaign" in m["workloads"]}
    want = listed if trace else {"tasks_per_s", "setup_s"}
    assert set(res["metrics"]) == want
    if trace:
        # wide bundles: 1,920 tasks a unit in bundles of up to 256
        assert res["metrics"]["bundle_tasks_mean.batch"]["value"] > 100
        assert res["metrics"]["compiles_in_window.batch"]["value"] == 0


@pytest.mark.parametrize("name", ["moldyn-244.campaign", "fmri-airsn.stream"])
def test_span_report_reads_every_span_metric(name):
    if name.startswith("moldyn"):
        cell = tiny(name, molecules=3, state_rows=8, state_width=128,
                    max_bundle=64)
    else:
        cell = tiny(name, bank_volumes=10, run_volumes=6, **FMRI_TINY)
        cell.traffic.update(rate_per_s=40.0,
                            warm_up={"seconds": 0.3, "bursts": 3,
                                     "device_bursts": 3})
    out = span_report.report(cell, 2**31 + 23, 1.0, FakeTpu(), 1)
    assert out["correct"] is True, out["checks"]
    split = "stream" if name.startswith("fmri") else "batch"
    for base in span_report.SPAN_METRICS[split]:
        assert out["metrics"][f"{base}.{split}"] > 0, base
    # the end-to-end metrics are read in the traced run too
    assert out["metrics"]["tasks_per_s"] > 0
    if split == "stream":
        assert out["metrics"]["latency_p50_s"] > 0
    assert 0 < out["clock_thread_spans_per_task"] <= 8
    assert out["spans"]["pool.bundle"]["count"] >= 1
    # the reduction is the harness's own again once the run is over
    assert trace_reduce.reduce.__module__ == "chipbench.trace_reduce"


def test_span_report_records_a_small_trace(tmp_path):
    out = tmp_path / "spans.json"
    span_report.record(str(out), (
        ("moldyn-244.campaign", dict(molecules=2, state_rows=8,
                                     state_width=128), {"chains": True}, 1),
        ("fmri-airsn.stream", FMRI_TINY, {"volumes": 1}, 2)))
    spans = json.loads(out.read_text())["spans"]
    names = {sp[1] for sp in spans}
    assert {"bundle.call", "bundle.single", "engine.ready"} <= names
    assert min(sp[2] for sp in spans) == 0.0
