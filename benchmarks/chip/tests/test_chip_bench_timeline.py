"""Per-token timing derived from the engine's public API, checked against
the engine's own slot state on the smoke presets (CPU)."""
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import harness, timeline  # noqa: E402
from benchmarks.chip.traffic import Traffic  # noqa: E402


def _program(cell_name, **engine):
    cell = harness.load_cell(ROOT, cell_name)
    cell.engine = dict(cell.engine, **engine)
    harness.start(cell, require_tpu=False)
    program = harness.Program(cell, smoke=True)
    program.load(seed=5)
    return cell, program


def _record_slots(eng):
    """Wrap eng.step to record, after each step, every slot's emitted
    count (test-only look at the engine's private slot state)."""
    seen = []
    step = eng.step

    def recording():
        out = step()
        seen.append({s.req.uid: len(s.out) for s in eng._slots if s})
        for r in out:
            seen[-1][r.uid] = len(r.out_tokens)
        return out

    eng.step = recording
    return seen


@pytest.mark.parametrize("arrival", [{"kind": "poisson", "rate_per_s": 40.0},
                                     {"kind": "backlog"}])
def test_derived_first_token_step_matches_slot_state(arrival):
    cell, program = _program("granite-moe.short-poisson")
    seen = _record_slots(program.eng)
    mix = dict(cell.mix, requests=64, arrival=arrival)
    traffic = Traffic(mix, program.arch.vocab, seed=11)
    drv = harness.Driver(program.eng, traffic, cell)
    drv.run(1.0, None)
    while program.eng.pending:          # finish what was queued, add nothing
        drv.step()
    seen = seen[-len(drv.steps):]
    done = [r for r in drv.served.values() if r.done]
    assert len(done) >= 4
    for r in done:
        first_seen = next(i for i, slots in enumerate(seen)
                          if slots.get(r.uid, 0) >= 1)
        assert r.first_step() == first_seen
        assert seen[r.finish_step][r.uid] == len(r.out)
    # With every request finished, the derived counts equal the engine's.
    emit, busy = timeline.implied_counts(drv.steps, drv.served, drv.chunk)
    assert list(emit) == [s.tokens for s in drv.steps]
    assert list(busy) == [s.live for s in drv.steps]
    assert timeline.explained(drv.steps, drv.served, drv.chunk, 0,
                              len(drv.steps))
    # Work rows: every prompt chunk and every decode token, once.
    rows = timeline.step_rows(drv.served, drv.chunk, 0, len(drv.steps))
    total = sum(q for step in rows for q, _ in step)
    assert total == sum(r.prompt_len + len(r.out) - 1 for r in done)


def test_itl_gaps_from_step_counts():
    S = timeline.Step
    steps = [S(0, 1.0, "prefill", 2, 2, 0, 0, 0, 1),
             S(1, 1.5, "decode", 2, 2, 1, 0, 0, .5),
             S(1.5, 1.75, "decode", 1, 1, 1, 0, 0, .25)]
    gaps, counts = timeline.itl_gaps(steps, 0, 3)
    # Two requests emit at step 0; both emit again at step 1 (one finishes);
    # the other emits at step 2.
    assert list(gaps) == [0.5, 0.25] and list(counts) == [2, 1]
    assert timeline.weighted_percentile(gaps, counts, 50) == 0.5
    assert timeline.weighted_percentile(np.array([]), np.array([]), 50) is None


def test_preemption_and_inconsistent_counts_raise():
    cell, program = _program("granite-moe.short-poisson", max_pages=60)
    mix = dict(cell.mix, requests=32, arrival={"kind": "backlog"})
    drv = harness.Driver(program.eng, Traffic(mix, program.arch.vocab, 3),
                         cell)
    with pytest.raises(timeline.TimelineError, match="preempted"):
        drv.run(30.0, None)
    S = timeline.Step
    steps = [S(0, 1, "prefill", 1, 1, 1, 0, 0, 1)]
    served = {0: timeline.Served(uid=0, prompt_len=64, max_new=4, due=None,
                                 added_step=0, finish_step=0, out=[5, 6])}
    with pytest.raises(timeline.TimelineError):
        timeline.implied_counts(steps, served, 64)
    served[0] = dataclasses.replace(served[0], out=[5])
    assert timeline.explained(steps, served, 64, 0, 1)
