"""Median wall time of the engine's decode steps (``StepTelemetry.wall_s``,
the engine's own span around ``step()``), over the traced span."""
import numpy as np


def read(run):
    walls = [s.wall_s for s in run.layer_steps() if s.phase == "decode"]
    return float(np.median(walls)) * 1e3 if walls else None
