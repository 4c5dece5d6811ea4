"""Attribute a traced window of one cell to the engine's own spans and
request stamps.

    python3 -m benchmarks.chip.servespans --workload <cell> --seed <n> \\
        [--seconds <s>] [--out <dir>]

The harness's reduction (``devtrace.py``) labels the chip's idle time by
the harness's ``bench.*`` spans alone, so every gap inside a step reads
``bench.engine_step``.  This tool drives the cell through the harness's
``Driver`` with an ``obs.Tracer`` installed, so the engine's
``serve.step`` span and its children (``serve.admit``,
``serve.schedule``, ``serve.dispatch``, ``serve.sync``, ``serve.commit``,
``serve.telemetry``) land on the host plane of the profiler trace beside
the device's ops, and it keeps every request the engine was given, with
its host-clock stamps (``t_queued``, ``t_admitted``, ``t_first_token``).
From the trace and the stamps it reads:

* for each span name, how many spans lie wholly inside ``bench.traced``,
  their host time and the device idle time inside them (mean over chips);
* the idle time of the traced span split by the innermost span open, and
  the longest idle gaps labelled the same way;
* ``host_gap_ms``: device idle inside ``serve.step`` per step;
* ``queue_wait_p90_ms`` (``t_queued`` to ``t_admitted``),
  ``prefill_p90_ms`` (``t_admitted`` to ``t_first_token``) and
  ``ttft_p90_ms`` (due to the end of the first-token step, as the
  benchmark's reader), over the requests whose first token came inside
  the traced span.

The last line of standard output is one JSON object; the trace stays
under ``--out``.  The tracer is on for the whole window, so these runs
are not the benchmark's gated runs.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.chip import devtrace

SERVE_PREFIX = "serve."
STEP_SPAN = "serve.step"
OUTSIDE = "outside harness spans"
Span = Tuple[float, float, str]


@dataclasses.dataclass
class SpanTime:
    count: int            # spans of one name wholly inside the traced span
    total_s: float        # their host time
    idle_s: float         # device idle inside them, mean over chips


@dataclasses.dataclass
class Attribution:
    window_s: float                      # length of the traced span
    idle_s: float                        # device idle in it, mean over chips
    chips: int
    spans: Dict[str, SpanTime]
    idle_by_label: Dict[str, float]      # innermost span -> idle
    idle_gaps: List[Tuple[str, float]]   # longest gaps, by innermost span


def host_spans(pd) -> List[Span]:
    """The harness's ``bench.*`` and the engine's ``serve.*`` spans."""
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith((devtrace.SPAN_PREFIX, SERVE_PREFIX))]


def _device_idle(pd, lo: float, hi: float) -> List[List[Tuple[float, float]]]:
    """Per chip, the sorted idle intervals of [lo, hi]: where no op on
    the chip's ``XLA Ops`` line ran."""
    chips = []
    for plane in pd.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        ivals = [(max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi))
                 for line in plane.lines if line.name == devtrace.OPS_LINE
                 for ev in line.events]
        merged = devtrace._union([(a, b) for a, b in ivals if b > a])
        if not merged:
            continue
        idle, prev = [], lo
        for a, b in merged + [(hi, hi)]:
            if a > prev:
                idle.append((prev, a))
            prev = max(prev, b)
        chips.append(idle)
    return chips


def _idle_before(idle: List[Tuple[float, float]], ts) -> np.ndarray:
    """Idle time from the first idle interval up to each time in ``ts``;
    ``idle`` is sorted and disjoint."""
    ts = np.asarray(ts, np.float64)
    if not idle:
        return np.zeros_like(ts)
    a = np.array([x for x, _ in idle])
    d = np.array([y - x for x, y in idle])
    cum = np.concatenate([[0.0], np.cumsum(d)])
    j = np.searchsorted(a, ts, side="right")
    k = np.maximum(j - 1, 0)
    part = np.where(j > 0, np.clip(ts - a[k], 0.0, d[k]), 0.0)
    return cum[k] + part


def _segments(spans: List[Span], lo: float,
              hi: float) -> Tuple[List[float], List[str]]:
    """Cut [lo, hi] at every span edge: (edges, innermost span name of
    each piece between two edges)."""
    inner = [(max(a, lo), min(b, hi), b - a, n) for a, b, n in spans
             if n != devtrace.TRACED_SPAN and min(b, hi) > max(a, lo)]
    edges = sorted({lo, hi, *(a for a, _, _, _ in inner),
                    *(b for _, b, _, _ in inner)})
    by_start = sorted(inner)
    names, active, i = [], [], 0
    for p in edges[:-1]:
        while i < len(by_start) and by_start[i][0] <= p:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s[1] > p]
        names.append(min(active, key=lambda s: s[2])[3] if active
                     else OUTSIDE)
    return edges, names


def reduce(pd) -> Optional[Attribution]:
    """Attribute the traced span of ``pd`` to the spans open in it.  None
    when the trace holds no ``bench.traced`` span or no device op in it."""
    spans = host_spans(pd)
    traced = [(a, b) for a, b, n in spans if n == devtrace.TRACED_SPAN]
    if not traced:
        return None
    lo, hi = traced[0]
    chips = _device_idle(pd, lo, hi)
    if not chips:
        return None
    whole = [(a, b, n) for a, b, n in spans
             if n != devtrace.TRACED_SPAN and a >= lo and b <= hi]
    edges, names = _segments(spans, lo, hi)
    span_idle = np.zeros(len(whole))
    label_idle = defaultdict(float)
    gaps = []
    for idle in chips:
        if whole:
            span_idle += (_idle_before(idle, [b for _, b, _ in whole])
                          - _idle_before(idle, [a for a, _, _ in whole]))
        for name, t in zip(names, np.diff(_idle_before(idle, edges))):
            label_idle[name] += float(t)
        gaps += [(b - a, (a + b) / 2) for a, b in idle]
    n = len(chips)
    by_name: Dict[str, SpanTime] = {}
    for (a, b, name), t in zip(whole, span_idle):
        st = by_name.setdefault(name, SpanTime(0, 0.0, 0.0))
        st.count += 1
        st.total_s += (b - a) / 1e9
        st.idle_s += float(t) / n / 1e9
    piece = np.searchsorted(edges, [mid for _, mid in gaps], side="right") - 1
    gaps = sorted(zip((d for d, _ in gaps),
                      np.minimum(piece, len(names) - 1)), reverse=True)
    return Attribution(
        window_s=(hi - lo) / 1e9,
        idle_s=sum(b - a for idle in chips for a, b in idle) / n / 1e9,
        chips=n, spans=by_name,
        idle_by_label={k: t / n / 1e9 for k, t in label_idle.items() if t > 0},
        idle_gaps=[(names[k], d / 1e9) for d, k in gaps[:devtrace.TOP]])


def host_gap_ms(att: Attribution) -> Optional[float]:
    """Device idle inside ``serve.step`` per step; None without steps."""
    step = att.spans.get(STEP_SPAN)
    return 1e3 * step.idle_s / step.count if step else None


def p90_ms(values) -> Optional[float]:
    return float(np.percentile(values, 90)) * 1e3 if len(values) else None


def first_token_by(requests, end: float) -> list:
    """Requests whose engine stamps say their first token came by ``end``
    (host clock)."""
    return [r for r in requests
            if r.t_first_token is not None and r.t_first_token <= end]


def stamp_p90s(requests, end: float) -> Dict[str, Optional[float]]:
    """``queue_wait_p90_ms`` and ``prefill_p90_ms`` over the requests whose
    first token came by ``end``."""
    reqs = first_token_by(requests, end)
    return {"queue_wait_p90_ms": p90_ms([r.t_admitted - r.t_queued
                                         for r in reqs]),
            "prefill_p90_ms": p90_ms([r.t_first_token - r.t_admitted
                                      for r in reqs])}


class Recording:
    """An engine that keeps every request it is given (with the stamps the
    engine sets on it); everything else passes through."""

    def __init__(self, eng):
        self._eng = eng
        self.requests: list = []

    def add_request(self, req, *args, **kwargs):
        uid = self._eng.add_request(req, *args, **kwargs)
        self.requests.append(req)
        return uid

    def __getattr__(self, name):
        return getattr(self._eng, name)


def attribute(workload: str, seed: int, seconds: float, out_dir: str, *,
              root: str, require_tpu: bool = True,
              smoke: bool = False) -> dict:
    """One traced window of ``workload`` with the engine's spans on;
    returns the attribution as a JSON-ready dict.  The profiler trace is
    written under ``out_dir``."""
    from jax.profiler import ProfileData

    from benchmarks.chip import harness
    from benchmarks.chip.traffic import Traffic
    from repro import obs
    cell = harness.load_cell(root, workload)
    harness.start(cell, require_tpu)
    program = harness.Program(cell, smoke=smoke)
    program.load(seed)
    eng = Recording(program.eng)
    driver = harness.Driver(eng, Traffic(cell.mix, program.arch.vocab, seed),
                            cell)
    prev = obs.set_tracer(obs.Tracer())
    try:
        win = driver.run(seconds, out_dir)
    finally:
        obs.set_tracer(prev)
    pd = ProfileData.from_file(devtrace.find_xplane(out_dir))
    att = reduce(pd)
    steps = [driver.steps[i] for i in win.traced]
    end = steps[-1].t_end if steps else -np.inf
    out = {"workload": workload, "seed": seed, "traced_steps": len(steps),
           **stamp_p90s(eng.requests, end)}
    due = {r.uid: r.due for r in driver.served.values()}
    out["ttft_p90_ms"] = p90_ms(
        [driver.steps[_step_of(driver.steps, r.t_first_token)].t_end
         - due[r.uid] for r in first_token_by(eng.requests, end)
         if due.get(r.uid) is not None])
    red = devtrace.reduce(pd, {})
    if att is not None and red is not None:
        # The idle time device_idle_share counts: the traced span less the
        # harness's waits for arrivals, less the busy time.
        serving_idle = red.window_s - red.waiting_s - red.busy_s
        step = att.spans.get(STEP_SPAN)
        out.update(
            host_gap_ms=host_gap_ms(att), window_s=att.window_s,
            device_idle_share=100.0 * serving_idle / (red.window_s
                                                      - red.waiting_s),
            step_idle_share_of_idle=(100.0 * step.idle_s / serving_idle
                                     if step and serving_idle > 0 else None),
            idle_s=att.idle_s, chips=att.chips,
            spans={k: dataclasses.asdict(v)
                   for k, v in sorted(att.spans.items())},
            idle_by_label=dict(sorted(att.idle_by_label.items(),
                                      key=lambda kv: -kv[1])),
            idle_gaps=att.idle_gaps)
    return out


def _step_of(steps, t: float) -> int:
    """Index of the step whose host span holds time ``t``."""
    return int(np.searchsorted([s.t_end for s in steps], t, side="left"))


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import time
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window (default: the harness's traced span)")
    ap.add_argument("--out", default=None,
                    help="directory for the profiler trace "
                         "(default: chiprun_out/servespans/<cell>-<seed>)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(root, "src"), root]
    from benchmarks.chip import harness
    out_dir = args.out or os.path.join(
        root, "chiprun_out", "servespans", f"{args.workload}-{args.seed}")
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    try:
        result = attribute(args.workload, args.seed,
                           args.seconds or harness.TRACE_SECONDS, out_dir,
                           root=root)
    except harness.NoChip as e:
        print(f"[servespans] no result: {e}", file=sys.stderr, flush=True)
        return 3
    result["run_s"] = time.perf_counter() - t0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
