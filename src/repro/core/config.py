"""Global runtime configuration for the MPGEMM op layer.

Backend dispatch:
  * ``pallas``     — real Mosaic lowering (TPU runtime).
  * ``interpret``  — Pallas interpret mode (CPU correctness tests).
  * ``xla``        — plain XLA dot_general with the same precision semantics
                     (CPU dry-runs / AOT compiles; also the fallback any time
                     a GEMM shape is degenerate).

The dry-run lowers the ``xla`` path: cost_analysis FLOPs/bytes are identical
to the kernel path, and the Mosaic kernel cannot lower to the CPU backend.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax

_state = threading.local()

_VALID = ("auto", "pallas", "interpret", "xla")

# The checkout root (src/repro/core/config.py -> three levels up).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Entry points (``launch/serve.py``, ``launch/train.py``,
    ``chip_smoke.py``, ``benchmarks/run.py``) call this once before they
    compile; importing the package never does.  A set
    ``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set (JAX reads
    the variable itself).  Otherwise the cache lives in
    ``<checkout>/.jax_cache``: a fixed path, since the path is part of what
    a later run looks up.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _default_backend() -> str:
    env = os.environ.get("REPRO_GEMM_BACKEND", "auto")
    return env if env in _VALID else "auto"


def get_gemm_backend() -> str:
    backend = getattr(_state, "backend", None) or _default_backend()
    if backend == "auto":
        platform = jax.default_backend()
        backend = "pallas" if platform == "tpu" else "xla"
    return backend


@contextlib.contextmanager
def gemm_backend(name: str):
    """Context manager: force the GEMM backend (tests use ``interpret``)."""
    if name not in _VALID:
        raise ValueError(f"unknown backend {name!r}; valid: {_VALID}")
    prev = getattr(_state, "backend", None)
    _state.backend = name
    try:
        yield
    finally:
        _state.backend = prev


# --- fused-epilogue toggle ---------------------------------------------------

def fused_epilogues() -> bool:
    """Should model layers fuse gated-activation / residual epilogues?

    Default ON (the registry epilogues ride the GEMM's accumulator store —
    core/gemm_spec.py).  ``REPRO_FUSED_EPILOGUE=0`` or the
    :func:`fused_epilogue` context disable it, which the fused-vs-unfused
    benchmark (benchmarks/bench_epilogue.py) uses for its A/B.  Read at
    trace time, so functions jitted under one setting keep it.
    """
    val = getattr(_state, "fused_epilogue", None)
    if val is not None:
        return val
    return os.environ.get("REPRO_FUSED_EPILOGUE", "1").lower() not in (
        "0", "false", "off")


@contextlib.contextmanager
def fused_epilogue(enabled: bool):
    """Context manager: force epilogue fusion on/off for traces inside."""
    prev = getattr(_state, "fused_epilogue", None)
    _state.fused_epilogue = bool(enabled)
    try:
        yield
    finally:
        _state.fused_epilogue = prev
