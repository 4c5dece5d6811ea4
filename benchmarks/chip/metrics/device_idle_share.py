"""Share of the traced span in which no operation ran on the chip while
the engine had work: the harness's waits for arrivals of an open-loop
mix (``bench.wait``) are left out of the span."""


def read(run):
    if run.trace is None:
        return None
    serving = run.trace.window_s - run.trace.waiting_s
    return 100.0 * (1.0 - run.trace.busy_s / serving)
