"""ServeEngine's spans on the JAX profiler's clock and its per-request
host-clock stamps (serve/engine.py, obs/trace.py), read back from a CPU
``jax.profiler`` trace."""
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import obs
from repro.obs.trace import Tracer, set_tracer

CHILDREN = ["serve.admit", "serve.schedule", "serve.dispatch", "serve.sync",
            "serve.commit", "serve.telemetry"]


@pytest.fixture(scope="module")
def engine_setup():
    from repro.configs import base as cb
    from repro.models.transformer import build_model
    cfg = cb.get("phi3-mini-3.8b", smoke=True)
    model = build_model(cfg, policy="bf16", remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture
def registry():
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    try:
        yield reg
    finally:
        obs.set_registry(prev)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        2, cfg.vocab, (n,)).astype(np.int32)


def _drain(eng, limit=300):
    done = {}
    for _ in range(limit):
        for r in eng.step():
            done[r.uid] = r
        if not eng.pending:
            return done
    raise AssertionError("engine did not drain")


def _host_events(logdir, prefix):
    """(name, start_ns, end_ns, stats) of the host events named prefix*,
    in order of start (an enclosing span first)."""
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(prefix)]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def _profiled(logdir, fn):
    """Run fn with an ambient Tracer inside a CPU profiler trace."""
    tracer = Tracer()
    prev = set_tracer(tracer)
    jax.profiler.start_trace(str(logdir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
        set_tracer(prev)
    return tracer, out


def test_span_and_annotation_reach_the_profiler(tmp_path):
    def body():
        with obs.span("serve.outer", layer=3):
            with obs.span("serve.inner"):
                obs.annotate(finished=2)
    tracer, _ = _profiled(tmp_path, body)
    ev = {n: (a, b, s) for n, a, b, s in _host_events(tmp_path, "serve.")}
    assert set(ev) == {"serve.outer", "serve.inner"}
    (oa, ob, os_), (ia, ib, is_) = ev["serve.outer"], ev["serve.inner"]
    assert oa <= ia <= ib <= ob
    assert os_ == {"layer": 3} and is_ == {"finished": 2}
    # The in-memory Chrome export keeps the same spans and args.
    chrome = {e["name"]: e["args"] for e in tracer.events()}
    assert chrome == {"serve.outer": {"layer": 3},
                      "serve.inner": {"finished": 2}}


def test_engine_step_span_tree(engine_setup, tmp_path, registry):
    from repro.serve.engine import ServeEngine
    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8,
                      prefill_chunk=4)
    for i, n in enumerate((8, 6, 4)):
        eng.add_request(_prompt(cfg, n, i), max_new_tokens=3, uid=i)
    _, done = _profiled(tmp_path, lambda: _drain(eng))
    events = _host_events(tmp_path, "serve.")
    steps = [e for e in events if e[0] == "serve.step"]
    tel = eng.step_telemetry
    assert len(steps) == len(tel) and len(done) == 3
    widths_seen = set()
    for (_, a, b, args), rec in zip(steps, tel):
        assert args == {"step": rec.step, "phase": rec.phase,
                        "live": rec.live, "chunk": args["chunk"]}
        assert args["chunk"] == (1 if rec.phase == "decode" else 4)
        inside = [e for e in events if a <= e[1] and e[2] <= b
                  and e[0] != "serve.step"]
        assert [e[0] for e in inside] == CHILDREN
        kid = {e[0]: e[3] for e in inside}
        assert kid["serve.admit"]["step"] == rec.step
        assert kid["serve.schedule"] == {"preempted": rec.preemptions}
        assert kid["serve.dispatch"] == {
            "new_shape": int(args["chunk"] not in widths_seen),
            "pages_walked": kid["serve.dispatch"]["pages_walked"]}
        assert 1 <= kid["serve.dispatch"]["pages_walked"] <= 2 * 8
        widths_seen.add(args["chunk"])
        # Children run one after another.
        assert all(x[2] <= y[1] for x, y in zip(inside, inside[1:]))
    assert sum(e[3]["admitted"] for e in events
               if e[0] == "serve.admit") == 3
    assert sum(e[3]["finished"] for e in events
               if e[0] == "serve.commit") == 3
    shapes = registry.counter("serve_new_step_shapes_total")
    assert shapes.value(chunk=4) == 1.0 and shapes.value(chunk=1) == 1.0
    assert registry.counter("serve_attention_pages_walked_total").value() \
        == sum(e[3]["pages_walked"] for e in events
               if e[0] == "serve.dispatch")
    assert registry.counter("serve_attention_table_slots_total").value() \
        == len(steps) * 2 * 8


def test_attention_page_walk_counters(engine_setup, registry):
    """Each step counts every row's live pages after its writes, and the
    max_batch x table-width slots the kernel no longer walks."""
    from repro.serve.engine import ServeEngine
    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8,
                      prefill_chunk=8)
    eng.add_request(_prompt(cfg, 7, 0), max_new_tokens=3)
    _drain(eng)
    # Lengths 7, 8, 9 after the prefill and two decode steps: 1 + 1 + 2.
    assert len(eng.step_telemetry) == 3
    assert registry.counter(
        "serve_attention_pages_walked_total").value() == 4.0
    assert registry.counter(
        "serve_attention_table_slots_total").value() == 3 * 2 * 8


def test_expert_row_counters(registry):
    """A MoE model's steps count their live (token, expert) rows and the
    rows of the expert-sorted buffer, per MoE layer, by phase."""
    from repro.configs import base as cb
    from repro.kernels.mpgemm import ragged_rows
    from repro.models.transformer import build_model
    from repro.serve.engine import ServeEngine
    cfg = cb.get("granite-moe-1b-a400m", smoke=True)
    model = build_model(cfg, policy="bf16", remat=False)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_len=64,
                      max_batch=2, page_size=8, prefill_chunk=8)
    eng.add_request(_prompt(cfg, 7, 0), max_new_tokens=3)
    _drain(eng)
    # A prefill step of width 7 (7 live tokens), then two decode steps.
    assert [s.phase for s in eng.step_telemetry] == [
        "prefill", "decode", "decode"]
    k, e = cfg.experts_per_token, cfg.n_experts

    def buffer(tokens):
        return ragged_rows(tokens * k, e)

    live = registry.counter("serve_expert_rows_live_total")
    rows = registry.counter("serve_expert_rows_buffer_total")
    assert live.value(phase="prefill") == 7 * k
    assert live.value(phase="decode") == 2 * k
    assert rows.value(phase="prefill") == buffer(2 * 7) == 120
    assert rows.value(phase="decode") == 2 * buffer(2 * 1) == 2 * 72


def test_untraced_step_records_nothing(engine_setup, tmp_path):
    from repro.serve.engine import ServeEngine
    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8)
    eng.add_request(_prompt(cfg, 6, 0), max_new_tokens=2)
    assert obs.get_tracer() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drain(eng)
    finally:
        jax.profiler.stop_trace()
    assert _host_events(tmp_path, "serve.") == []


def test_request_stamps_and_histograms(engine_setup, registry):
    from repro.serve.engine import ServeEngine
    cfg, model, params = engine_setup
    # A pool too small for both requests' full lengths forces preemption.
    eng = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8,
                      max_pages=8)
    queued = {}
    for uid in (0, 1):
        eng.add_request(_prompt(cfg, 10, uid + 4), max_new_tokens=16,
                        uid=uid)
        queued[uid] = eng._queue[-1].t_queued
    done = _drain(eng)
    preempted = sum(s.preemptions for s in eng.step_telemetry)
    assert preempted > 0
    assert sum(r.preemptions for r in done.values()) == preempted
    for uid, r in done.items():
        assert r.t_queued == queued[uid]        # kept across preemption
        assert r.t_queued <= r.t_admitted <= r.t_first_token
    wait = registry.histogram("serve_queue_wait_seconds").snapshot_one()
    prefill = registry.histogram("serve_prefill_seconds").snapshot_one()
    assert wait["count"] == prefill["count"] == 2
    assert prefill["sum"] == pytest.approx(
        sum(r.t_first_token - r.t_admitted for r in done.values()))
