"""Process start to the window's start: imports, device start-up, weights,
KV pool, warm-up (and compilation where the cache misses)."""


def read(run):
    return run.setup_s
