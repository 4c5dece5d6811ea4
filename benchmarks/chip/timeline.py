"""Per-token times and per-step work, derived from the engine's public API.

The harness sees each ``ServeEngine.step()``: its host start and end, its
``StepTelemetry`` (live slots, tokens emitted) and the requests it
finished.  With prompt lengths that are multiples of the prefill chunk,
the engine's schedule is fixed by those numbers:

* a request is prefilled in consecutive steps, one chunk per step, from
  the step that admits it; the step that takes its last chunk emits its
  first token;
* from then on it emits one token every step until it finishes, unless
  it is preempted (the harness asserts that none is).

So a request that finishes at step F with n tokens emitted its first at
step F - n + 1 and began its prefill P/chunk - 1 steps earlier.  The
counts that these timelines imply are checked against the telemetry of
every step: they may never exceed it, and they must equal it on every
step whose requests have all finished.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


class TimelineError(RuntimeError):
    """The engine did not follow the schedule the derivation assumes."""


@dataclasses.dataclass
class Step:
    t_start: float        # host clock before step()
    t_end: float          # host clock after step() returned its tokens
    phase: str            # StepTelemetry.phase
    live: int             # StepTelemetry.live
    tokens: int           # StepTelemetry.tokens
    finished: int         # requests step() returned
    preemptions: int      # StepTelemetry.preemptions
    page_occupancy: float  # StepTelemetry.page_occupancy
    wall_s: float         # StepTelemetry.wall_s


@dataclasses.dataclass
class Served:
    uid: int
    prompt_len: int
    max_new: int
    due: Optional[float]   # host time the request was due (open loop)
    added_step: int        # index of the first step after it was queued
    finish_step: int = -1
    out: Optional[List[int]] = None

    @property
    def done(self) -> bool:
        return self.finish_step >= 0

    def first_step(self) -> int:
        return self.finish_step - len(self.out) + 1

    def prefill_start(self, chunk: int) -> int:
        return self.first_step() - self.prompt_len // chunk + 1


def implied_counts(steps: List[Step], served: Dict[int, Served],
                   chunk: int) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens emitted, slots busy) per step implied by finished requests.

    Raises TimelineError where they exceed what the engine reported, or
    where a request would have started before it was queued."""
    n = len(steps)
    emit = np.zeros(n, np.int64)
    busy = np.zeros(n, np.int64)
    for r in served.values():
        if not r.done:
            continue
        if r.prompt_len % chunk:
            raise TimelineError(f"request {r.uid}: prompt {r.prompt_len} is "
                                f"not a multiple of the chunk {chunk}")
        first, start = r.first_step(), r.prefill_start(chunk)
        if start < r.added_step:
            raise TimelineError(
                f"request {r.uid}: derived prefill start {start} precedes "
                f"its queueing before step {r.added_step}")
        emit[first:r.finish_step + 1] += 1
        busy[start:r.finish_step + 1] += 1
    tokens = np.array([s.tokens for s in steps], np.int64)
    live = np.array([s.live for s in steps], np.int64)
    bad = np.flatnonzero((emit > tokens) | (busy > live))
    if bad.size:
        s = int(bad[0])
        raise TimelineError(
            f"step {s}: finished requests imply {emit[s]} tokens and "
            f"{busy[s]} busy slots, the engine reported {tokens[s]} and "
            f"{live[s]}")
    return emit, busy


def explained(steps: List[Step], served: Dict[int, Served], chunk: int,
              lo: int, hi: int) -> bool:
    """True when finished requests account for all work of steps [lo, hi)."""
    emit, busy = implied_counts(steps, served, chunk)
    return all(emit[s] == steps[s].tokens and busy[s] == steps[s].live
               for s in range(lo, hi))


def itl_gaps(steps: List[Step], lo: int, hi: int) -> Tuple[np.ndarray,
                                                           np.ndarray]:
    """(gap seconds, number of requests with that gap) for the token gaps
    that end in steps [lo + 1, hi).  A request that emitted in step s - 1
    and did not finish there emits again in step s."""
    durs, counts = [], []
    for s in range(lo + 1, hi):
        n = steps[s - 1].tokens - steps[s - 1].finished
        if n > 0:
            durs.append(steps[s].t_end - steps[s - 1].t_end)
            counts.append(n)
    return np.array(durs, np.float64), np.array(counts, np.int64)


def step_rows(served: Dict[int, Served], chunk: int, lo: int,
              hi: int) -> List[List[Tuple[int, int]]]:
    """Per step in [lo, hi): the (query tokens, first query position) of
    every slot that did work, from finished requests."""
    rows: List[List[Tuple[int, int]]] = [[] for _ in range(lo, hi)]
    for r in served.values():
        if not r.done:
            continue
        first, start = r.first_step(), r.prefill_start(chunk)
        for j in range(r.prompt_len // chunk):
            if lo <= start + j < hi:
                rows[start + j - lo].append((chunk, j * chunk))
        for i in range(1, len(r.out)):
            if lo <= first + i < hi:
                rows[first + i - lo].append((1, r.prompt_len + i - 1))
    return rows


def weighted_percentile(values: np.ndarray, counts: np.ndarray,
                        q: float) -> Optional[float]:
    """Percentile ``q`` (0-100) of ``values`` each repeated ``counts``."""
    if values.size == 0 or counts.sum() == 0:
        return None
    return float(np.percentile(np.repeat(values, counts), q))
