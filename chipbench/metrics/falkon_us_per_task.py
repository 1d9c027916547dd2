"""Host microseconds per task in the Falkon service: self time of the
``falkon.*`` spans (dispatch, pump, complete) on the profiler's clock
inside the window, over the tasks the pool ran in it."""
from chipbench.program_spans import per_task_us


def read(run):
    return per_task_us(run, ("falkon.",))
