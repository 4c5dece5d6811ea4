"""95th percentile of the gaps between consecutive tokens of a request,
over every such gap that ends inside the window (host clock)."""
from benchmarks.chip import timeline


def read(run):
    gaps, counts = timeline.itl_gaps(run.steps, 0, run.window.n_steps)
    p = timeline.weighted_percentile(gaps, counts, 95)
    return None if p is None else p * 1e3
