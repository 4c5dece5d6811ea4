"""Mean share of the KV pool's pages held per step
(``StepTelemetry.page_occupancy``, prefix-index pages included), over the
traced span."""


def read(run):
    steps = run.layer_steps()
    if not steps:
        return None
    return 100.0 * sum(s.page_occupancy for s in steps) / len(steps)
