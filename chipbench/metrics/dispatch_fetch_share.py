"""Share of the traced window the pool's dispatcher thread spends
fetching a bundle's results: waiting for the fused call's output, copying
it to the host and splitting it per task (self time of the
``bundle.wait``, ``bundle.d2h`` and ``bundle.split`` spans), in %."""
from chipbench.program_spans import window_share


def read(run):
    return window_share(run, ("bundle.wait", "bundle.d2h", "bundle.split"))
