"""Host microseconds per task in the engine: self time of the ``engine.*``
spans (submit, ready, dispatch, done) on the profiler's clock inside the
window, over the tasks the pool ran in it."""
from chipbench.program_spans import per_task_us


def read(run):
    return per_task_us(run, ("engine.",))
