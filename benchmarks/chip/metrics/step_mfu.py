"""Model FLOPs of the tokens the traced steps processed (``work.py``), over
the chip's bf16 peak times the traced span less the harness's waits for
arrivals (``bench.wait``)."""


def read(run):
    if run.trace is None or run.model_flops is None:
        return None
    serving = run.trace.window_s - run.trace.waiting_s
    return 100.0 * run.model_flops / (serving * run.peaks.bf16_flops)
