"""Median milliseconds a task waits in the device pool's queue: from its
hand-off to the pool (``started``) until the dispatcher takes its bundle
off the queue (``dequeued``), over the Tracer's spans ended in the window.
None where the program records no ``dequeued``."""
from chipbench.harness import percentile


def read(run):
    if not run.spans:
        return None
    waits = [sp.dequeued - sp.started for sp in run.spans
             if getattr(sp, "dequeued", 0.0) > 0.0]
    if not waits:
        return None
    return 1000.0 * percentile(waits, 0.50)
