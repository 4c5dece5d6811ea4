"""Structured tracing spans, on the JAX profiler's clock and in a
Chrome/Perfetto ``trace.json``.

``span("pack", bytes=...)`` opens a named region on the current thread;
spans nest through a contextvar.  Each engine step renders as
``serve.step`` ⊃ ``serve.admit``, ``serve.schedule``, ``serve.dispatch``,
``serve.sync``, ``serve.commit``, ``serve.telemetry``; the GEMM spans
(``gemm.plan`` ⊃ ``gemm.launch``, ``pack``, ``tune``) fire while jit
traces a program, not per step.

Every span is recorded twice while a tracer is installed:

* in the tracer's memory, host-side (``perf_counter_ns`` relative to
  tracer start), for ``launch/serve.py --trace-out`` and ``/trace``;
* as a ``jax.profiler.TraceAnnotation`` of the same name whose args are
  event stats, so a ``jax.profiler`` trace holds it on the host plane,
  on one clock with the device's ops.  Outside a profiler trace the
  annotation records nothing.

Tracing is OFF by default (the ambient tracer is None and the module
helpers are no-ops: ``span`` returns one shared ``nullcontext``);
``launch/serve.py --trace-out`` or ``set_tracer`` turn it on.  Events
accumulate in memory — the tracer is a recorder for bounded runs (a
serve smoke, a bench), not a streaming profiler.  jax is imported on
the first span, so importing this module stays stdlib-only.
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "Tracer",
    "annotate",
    "get_tracer",
    "instant",
    "set_tracer",
    "span",
    "tracing_enabled",
]

# Innermost-open-span stack for the current context (thread/task-local).
_span_stack: contextvars.ContextVar[Tuple[dict, ...]] = \
    contextvars.ContextVar("repro_obs_span_stack", default=())


class Tracer:
    """Collects complete ('X') and instant ('i') Chrome trace events."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._t0_ns = time.perf_counter_ns()
        self._pid = os.getpid()
        self._tid_names: Dict[int, int] = {}

    # -- internals ------------------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._tid_names:
                self._tid_names[ident] = len(self._tid_names)
            return self._tid_names[ident]

    # -- recording ------------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, category: str = "repro", **args: Any):
        from jax.profiler import TraceAnnotation
        annotation = TraceAnnotation(name, **args)
        rec = {"name": name, "cat": category, "args": dict(args),
               "ts": self._now_us(), "tid": self._tid(),
               "annotation": annotation}
        stack = _span_stack.get()
        token = _span_stack.set(stack + (rec,))
        annotation.__enter__()
        try:
            yield rec
        finally:
            annotation.__exit__(None, None, None)
            _span_stack.reset(token)
            dur = self._now_us() - rec["ts"]
            event = {"ph": "X", "name": name, "cat": category,
                     "ts": rec["ts"], "dur": dur, "pid": self._pid,
                     "tid": rec["tid"], "args": rec["args"]}
            with self._lock:
                self._events.append(event)

    def instant(self, name: str, category: str = "repro",
                **args: Any) -> None:
        event = {"ph": "i", "s": "t", "name": name, "cat": category,
                 "ts": self._now_us(), "pid": self._pid,
                 "tid": self._tid(), "args": dict(args)}
        with self._lock:
            self._events.append(event)

    def annotate(self, **args: Any) -> None:
        """Attach args to the innermost open span (no-op at top level)."""
        stack = _span_stack.get()
        if stack:
            stack[-1]["args"].update(args)
            stack[-1]["annotation"].set_metadata(**args)

    # -- export ---------------------------------------------------------------

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def chrome_trace(self) -> dict:
        """The ``trace.json`` payload Perfetto / chrome://tracing load."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


# --- the ambient tracer (None == tracing off) --------------------------------

_ambient_lock = threading.Lock()
_ambient: Optional[Tracer] = None


def get_tracer() -> Optional[Tracer]:
    return _ambient


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as ambient (None disables); returns previous."""
    global _ambient
    with _ambient_lock:
        prev = _ambient
        _ambient = tracer
    return prev


def tracing_enabled() -> bool:
    return _ambient is not None


_NULL_CM = contextlib.nullcontext()


def span(name: str, category: str = "repro", **args: Any):
    """Span on the ambient tracer; a shared no-op when tracing is off."""
    tracer = _ambient
    if tracer is None:
        return _NULL_CM
    return tracer.span(name, category, **args)


def instant(name: str, category: str = "repro", **args: Any) -> None:
    tracer = _ambient
    if tracer is not None:
        tracer.instant(name, category, **args)


def annotate(**args: Any) -> None:
    tracer = _ambient
    if tracer is not None:
        tracer.annotate(**args)
