"""Share of the padded elements that fused tasks of procedures declaring
uneven extents computed past their true extents, in %: 100 x (1 - sum of
true elements / sum of padded elements) over the Tracer's spans ended in
the window that carry those counts (shape-bucket padding and the dummy
rows of the K ladder).  None where the program records no such counts."""


def read(run):
    if not run.spans:
        return None
    true = padded = 0.0
    for sp in run.spans:
        p = getattr(sp, "padded_elements", 0.0)
        if p > 0.0:
            true += sp.true_elements
            padded += p
    if padded <= 0.0:
        return None
    return 100.0 * (1.0 - true / padded)
