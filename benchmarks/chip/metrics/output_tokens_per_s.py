"""Tokens the engine emitted in the window, over the window (host clock)."""


def read(run):
    tokens = sum(s.tokens for s in run.window_steps())
    return tokens / (run.window.end - run.window.t0)
