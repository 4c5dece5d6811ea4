"""90th percentile of the time to first token over every request of an
open-loop mix that was due in the window: from when it was due to the end
of the step that emitted its first token (host clock)."""
import numpy as np


def read(run):
    t = [run.steps[r.first_step()].t_end - r.due
         for r in run.served.values()
         if r.due is not None and r.due < run.window.deadline and r.done]
    return float(np.percentile(t, 90)) * 1e3 if t else None
