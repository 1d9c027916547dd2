"""The program's own layer spans in a JAX profiler trace, reduced to the
host seconds each layer and each dispatcher phase takes.

The system under test marks its layers with `repro.core.observability.span`
(a `jax.profiler.TraceAnnotation`): on the clock thread ``engine.*``,
``falkon.*``, ``pool.submit`` and ``pool.complete``; on the pool's
dispatcher thread ``pool.bundle`` and the ``bundle.*`` phases inside it.
Spans nest (``pool.complete`` ⊃ ``falkon.complete`` ⊃ ``engine.done`` ⊃ a
dependent's ``engine.ready`` ...), so a layer's cost is its spans' *self*
time: each span's duration less the part that spans nested in it on the
same thread line cover.

* `load(path)`: the program's spans of an ``.xplane.pb`` as
  ``[line, name, start_s, duration_s]``, `line` naming the thread line
  (its name and its place in the host plane, since the profiler gives
  Python threads no id);
* `self_seconds(spans)`: each span's self time, computed per thread line;
* `reduce(spans, lo, hi)`: per span name that starts inside ``[lo, hi)``,
  the count, total and self seconds, and the durations;
* `layer_self_s` / `per_task_us` / `window_share`: what the metric
  readers (``metrics/*_us_per_task.py``, ``metrics/dispatch_*_share.py``,
  ``metrics/singleton_ms.py``) take from ``run.trace["program_spans"]``;
* `gap_cover(trace, spans, lo, hi)`: for the longest idle gaps of the
  device, the share of each gap that each span name covers.

All times are in seconds, on the profiler's clock.
"""
from __future__ import annotations

PREFIXES = ("engine.", "falkon.", "pool.", "bundle.")


def is_program_span(name: str) -> bool:
    return name.startswith(PREFIXES)


def load(path: str) -> list:
    """The program's spans in the trace at `path`, every thread line of
    the host plane kept apart."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out: list = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for k, line in enumerate(plane.lines):
            key = f"{line.name}#{k}"
            out.extend([key, e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                       for e in line.events if is_program_span(e.name))
    return out


def self_seconds(spans: list) -> list:
    """Each span's duration less the part of it that the spans nested
    directly in it cover, per thread line; aligned with `spans`."""
    child = [0.0] * len(spans)
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], spans[i][2], -spans[i][3]))
    stack: list = []          # (end, index) of the open spans of one line
    line = None
    for i in order:
        ln, _name, s, d = spans[i]
        if ln != line:
            line, stack = ln, []
        while stack and stack[-1][0] <= s:
            stack.pop()
        e = s + d
        if stack:
            end, parent = stack[-1]
            child[parent] += min(e, end) - s
        stack.append((e, i))
    return [max(0.0, sp[3] - c) for sp, c in zip(spans, child)]


def reduce(spans: list, lo: float, hi: float) -> dict:
    """``{name: {"count", "total_s", "self_s", "durations"}}`` over the
    spans that start inside ``[lo, hi)``; nesting is read from every
    span, so a parent that started before `lo` still takes its part."""
    out: dict = {}
    for sp, own in zip(spans, self_seconds(spans)):
        _line, name, s, d = sp
        if not lo <= s < hi:
            continue
        r = out.get(name)
        if r is None:
            out[name] = r = {"count": 0, "total_s": 0.0, "self_s": 0.0,
                             "durations": []}
        r["count"] += 1
        r["total_s"] += d
        r["self_s"] += own
        r["durations"].append(d)
    return out


# -- what the metric readers take ------------------------------------------

def program_spans_of(run):
    """``run.trace["program_spans"]``, or None where the run has no trace
    or the trace holds none of the program's spans."""
    if run.trace is None:
        return None
    return run.trace.get("program_spans") or None


def layer_self_s(run, names) -> float | None:
    """Self seconds of the spans whose name is in `names` or starts with
    one of them ending in a dot; None when the run recorded none."""
    ps = program_spans_of(run)
    if ps is None:
        return None
    picked = [r for n, r in ps.items()
              if n in names or any(p.endswith(".") and n.startswith(p)
                                   for p in names)]
    if not picked:
        return None
    return sum(r["self_s"] for r in picked)


def per_task_us(run, names) -> float | None:
    """`layer_self_s` in microseconds per task the pool ran in the
    window."""
    s = layer_self_s(run, names)
    tasks = run.counters["tasks_run"]
    if s is None or not tasks:
        return None
    return 1e6 * s / tasks


def window_share(run, names) -> float | None:
    """`layer_self_s` over the traced window, in %."""
    s = layer_self_s(run, names)
    if s is None:
        return None
    return 100.0 * s / run.trace["window_s"]


# -- idle gaps --------------------------------------------------------------

def gap_cover(trace: dict, spans: list, lo: float, hi: float,
              top: int = 3) -> list:
    """The `top` longest idle gaps of the first device inside
    ``[lo, hi]``, longest first, as ``{"at_s", "seconds", "cover"}``:
    the gap's offset from `lo`, its length, and per span name (and
    ``"any program span"``) the share of the gap its spans cover."""
    from chipbench import trace_reduce

    planes = sorted(trace["devices"])
    if not planes:
        return []
    gaps, t = [], lo
    for s, e in trace_reduce.busy_intervals(trace, planes[0], lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        inside = [(n, s, d) for _line, n, s, d in spans
                  if s < g1 and s + d > g0]
        names = {n for n, _s, _d in inside}
        cover = {n: trace_reduce._union(
            [(s, d) for m, s, d in inside if m == n], g0, g1)
            for n in names}
        cover["any program span"] = trace_reduce._union(
            [(s, d) for _n, s, d in inside], g0, g1)
        share = {n: sum(e - s for s, e in ivs) / (g1 - g0)
                 for n, ivs in cover.items()}
        out.append({"at_s": g0 - lo, "seconds": g1 - g0,
                    "cover": dict(sorted(share.items(),
                                         key=lambda kv: -kv[1]))})
    return out
