"""Share of the traced window the pool's dispatcher thread spends
stacking a bundle's arguments and copying the stack to the device: self
time of the ``bundle.stack`` and ``bundle.h2d`` spans, in %."""
from chipbench.program_spans import window_share


def read(run):
    return window_share(run, ("bundle.stack", "bundle.h2d"))
