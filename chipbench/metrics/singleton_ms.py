"""Median milliseconds of a one-task bundle's eager body on the pool's
dispatcher thread: the ``bundle.single`` spans inside the window."""
from chipbench.harness import percentile
from chipbench.program_spans import program_spans_of


def read(run):
    ps = program_spans_of(run)
    if ps is None or "bundle.single" not in ps:
        return None
    return 1000.0 * percentile(ps["bundle.single"]["durations"], 0.50)
