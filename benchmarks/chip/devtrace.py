"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation the chip ran.  The harness wraps the traced steps in
a host span ``bench.traced`` and each thing it does in a span of its own
(``bench.engine_step``, ``bench.traffic``, ``bench.wait``, ...).  All
events are on one clock, in nanoseconds from the start of the trace.

On a TPU an op's event name is its HLO instruction
(``%closed_call.79 = bf16[...] custom-call(...), custom_call_target=
"tpu_custom_call", ...``).  A ``while`` (the scanned layer stack) spans
the ops of its body and counts for busy time only.

For the traced span this gives, averaged over the chips that ran work:
the busy time (the union of the op intervals), the time the harness
spent waiting for arrivals (``bench.wait``), the time of each kernel
class (ops whose name matches a class pattern), the ops that took most
time (by instruction name), and the longest idle gaps, each labelled by
the innermost harness span open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TRACED_SPAN = "bench.traced"
WAIT_SPAN = "bench.wait"
TOP = 10
# Control flow whose event spans the ops inside it.
CONTAINER = re.compile(r"\s(while|conditional|call)\(")


@dataclasses.dataclass
class Reduced:
    window_s: float                     # length of the traced span
    busy_s: float                       # union of op intervals, mean over chips
    waiting_s: float                    # harness idle, waiting for arrivals
    chips: int
    kernel_s: Dict[str, float]          # class -> summed op time, mean over chips
    device_ops: List[Tuple[str, float]]  # top ops by summed time
    idle_gaps: List[Tuple[str, float]]   # longest gaps, by host span


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _label(spans, t: float) -> str:
    """The innermost harness span (shortest) open at time t."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and name != TRACED_SPAN and (
                best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "outside harness spans"


def reduce(pd, classes: Dict[str, str]) -> Optional[Reduced]:
    """Reduce the traced span of ``pd``; ``classes`` maps a kernel class to
    a regular expression over op names.  None when the trace holds no
    ``bench.traced`` span or no device op inside it."""
    spans = _host_spans(pd)
    traced = [(a, b) for a, b, n in spans if n == TRACED_SPAN]
    if not traced:
        return None
    lo, hi = traced[0]
    waiting = sum(b - a for a, b in _union(
        [(max(a, lo), min(b, hi)) for a, b, n in spans
         if n == WAIT_SPAN and min(b, hi) > max(a, lo)]))
    pats = {k: re.compile(v) for k, v in classes.items()}
    busy = 0.0
    chips = 0
    kernel = defaultdict(float)
    ops = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ivals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, lo)
                b = min(ev.start_ns + ev.duration_ns, hi)
                if b <= a:
                    continue
                ivals.append((a, b))
                if CONTAINER.search(ev.name):
                    continue
                matched = [k for k, p in pats.items() if p.search(ev.name)]
                for k in matched:
                    kernel[k] += b - a
                label = ev.name.split(" = ")[0]
                ops[f"{label} [{matched[0]}]" if matched else label] += b - a
        if not ivals:
            continue
        chips += 1
        merged = _union(ivals)
        busy += sum(b - a for a, b in merged)
        prev = lo
        for a, b in merged + [(hi, hi)]:
            if a > prev:
                gaps.append((a - prev, (a + prev) / 2))
            prev = max(prev, b)
    if not chips:
        return None
    gaps = [(_label(spans, mid), d / 1e9)
            for d, mid in sorted(gaps, reverse=True)[:TOP]]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy / chips / 1e9,
        waiting_s=waiting / 1e9, chips=chips,
        kernel_s={k: kernel.get(k, 0.0) / chips / 1e9 for k in classes},
        device_ops=[(n, t / chips / 1e9) for n, t in top],
        idle_gaps=gaps)


def reduce_file(path: str, classes: Dict[str, str]) -> Optional[Reduced]:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), classes)
