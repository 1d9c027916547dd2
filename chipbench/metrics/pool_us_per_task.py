"""Host microseconds per task on the clock thread's side of the device
pool: self time of the ``pool.submit`` and ``pool.complete`` spans inside
the window, over the tasks the pool ran in it.  The dispatcher thread's
side is ``dispatcher_busy_share`` and the ``dispatch_*_share`` metrics."""
from chipbench.program_spans import per_task_us


def read(run):
    return per_task_us(run, ("pool.submit", "pool.complete"))
