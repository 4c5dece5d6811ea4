"""Mean share of the engine's slots doing work per step
(``StepTelemetry.live`` / max_batch), over the traced span."""


def read(run):
    steps = run.layer_steps()
    if not steps:
        return None
    slots = run.cell.engine["max_batch"]
    return 100.0 * sum(s.live for s in steps) / (len(steps) * slots)
