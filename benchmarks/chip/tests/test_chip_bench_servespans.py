"""servespans.py: the attribution of a traced window to the engine's
``serve.*`` spans on synthetic and recorded chip traces, what it reads
from the engine's request stamps, and a traced window of the smoke
preset on the CPU."""
import gzip
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from benchmarks.chip import devtrace, harness, servespans  # noqa: E402
from benchmarks.chip.traffic import Traffic  # noqa: E402
from repro.serve.engine import Request  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "granite-moe.short-poisson.3steps.xplane.pb.gz")
RECORDED_SPANS = os.path.join(
    DATA, "granite-moe.short-poisson.serve-spans.xplane.pb.gz")


def _load(path):
    with gzip.open(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


# ---------------------------------------------------------------- traces

# The engine's spans inside the harness's (ns): one step wholly inside the
# traced span [1000, 21000], a second that outlasts it.  Device ops
# [3500, 7000] and [11000, 20000]; idle [1000, 3500], [7000, 11000] and
# [20000, 21000].
SERVE = [("bench.traced", 1000, 21000), ("bench.engine_step", 2000, 10000),
         ("serve.step", 2200, 9800), ("serve.admit", 2200, 2600),
         ("serve.schedule", 2600, 3000), ("serve.dispatch", 3000, 4000),
         ("serve.sync", 4000, 8000), ("serve.commit", 8000, 9400),
         ("serve.telemetry", 9400, 9800), ("bench.traffic", 10000, 10500),
         ("bench.engine_step", 10500, 22000), ("serve.step", 10600, 21900)]


def _serve_trace():
    names = sorted({n for n, _, _ in SERVE})
    ids = {n: i + 1 for i, n in enumerate(names)}
    host = "".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {a * 1000} "
        f"duration_ps: {(b - a) * 1000} }}\n" for n, a, b in SERVE)
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    txt = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 3500000 duration_ps: 3500000 }}
    events {{ metadata_id: 1 offset_ps: 11000000 duration_ps: 9000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host}  }}
{meta}}}
'''
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))


def test_serve_spans_label_the_idle_time():
    r = servespans.reduce(_serve_trace())
    assert r.idle_s == pytest.approx(7.5e-6)
    assert r.idle_gaps == [("serve.commit", pytest.approx(4e-6)),
                           ("serve.admit", pytest.approx(2.5e-6)),
                           ("serve.step", pytest.approx(1e-6))]
    # Idle split by the innermost span open; it sums to all idle time.
    want = {servespans.OUTSIDE: 1000, "bench.engine_step": 500,
            "serve.admit": 400, "serve.schedule": 400,
            "serve.dispatch": 500, "serve.sync": 1000, "serve.commit": 1400,
            "serve.telemetry": 400, "bench.traffic": 500, "serve.step": 1400}
    assert r.idle_by_label == {k: pytest.approx(v * 1e-9)
                               for k, v in want.items()}
    assert sum(r.idle_by_label.values()) == pytest.approx(r.idle_s)


def test_serve_spans_wholly_inside_the_traced_span():
    r = servespans.reduce(_serve_trace())
    # Only the first step and the first bench.engine_step end inside it.
    step = r.spans["serve.step"]
    assert (step.count, step.total_s) == (1, pytest.approx(7.6e-6))
    assert step.idle_s == pytest.approx(4.1e-6)    # [2200, 3500] + [7000, 9800]
    assert r.spans["bench.engine_step"].idle_s == pytest.approx(4.5e-6)
    assert r.spans["serve.sync"].idle_s == pytest.approx(1e-6)
    assert r.spans["serve.dispatch"].idle_s == pytest.approx(0.5e-6)
    assert "bench.traced" not in r.spans
    assert servespans.host_gap_ms(r) == pytest.approx(4.1e-3)


def test_trace_without_a_traced_span_or_device_ops_reads_nothing():
    txt = '''
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } } }
'''
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))
    assert servespans.reduce(pd) is None


# What the harness's reduction reads on the recorded trace, pinned so
# that a change to how it counts shows.
RECORDED_BEFORE = {
    "window_s": 0.288589079, "busy_s": 0.273831616, "waiting_s": 0.0,
    "kernel_s": {"mpgemm": 0.021850186, "paged_attention": 0.169830416}}


@pytest.mark.parametrize("field", sorted(RECORDED_BEFORE))
def test_recorded_chip_trace_reads_as_before(field):
    classes = {}
    for name in ("mpgemm_roofline", "paged_attention_roofline"):
        classes.update(harness.load_reader(name).KERNELS)
    r = devtrace.reduce(_load(RECORDED), classes)
    want = RECORDED_BEFORE[field]
    got = getattr(r, field)
    if isinstance(want, dict):
        assert got == {k: pytest.approx(v, abs=1e-12) for k, v in want.items()}
    else:
        assert got == pytest.approx(want, abs=1e-12)


@pytest.mark.parametrize("path", [RECORDED, RECORDED_SPANS])
def test_idle_time_agrees_with_the_harness_reduction(path):
    pd = _load(path)
    att, red = servespans.reduce(pd), devtrace.reduce(pd, {})
    assert att.window_s == pytest.approx(red.window_s, abs=1e-12)
    assert att.idle_s == pytest.approx(red.window_s - red.busy_s, abs=1e-12)
    assert sum(att.idle_by_label.values()) == pytest.approx(att.idle_s)


def test_recorded_engine_spans_label_the_idle_time():
    """Three engine steps of granite-moe.short-poisson traced on a TPU v5e
    with the engine's ``serve.*`` spans (cut from a 10 s trace; host
    threads other than the harness's and op stats removed): the idle time
    inside ``bench.engine_step`` lies in the children of ``serve.step``."""
    r = servespans.reduce(_load(RECORDED_SPANS))
    assert r.spans["serve.step"].count == 3
    assert all(g[0].startswith("serve.") for g in r.idle_gaps)
    step_idle = r.spans["bench.engine_step"].idle_s
    own = r.idle_by_label.get("serve.step", 0.0) + r.idle_by_label.get(
        "bench.engine_step", 0.0)
    assert step_idle > 0 and own / step_idle < 0.05
    assert r.spans["serve.sync"].idle_s > 0
    assert r.spans["serve.dispatch"].idle_s > 0


# ---------------------------------------------------------------- stamps

def _request(uid, queued=None, admitted=None, first=None):
    return Request(uid=uid, prompt=np.zeros(4, np.int32), t_queued=queued,
                   t_admitted=admitted, t_first_token=first)


# Ten requests whose first token came in the traced span (up to 10 s):
# queued 1 ms .. 10 ms before a slot took them, prefilled in 0.05 s ..
# 0.5 s; one whose first token came after the span (it queued behind the
# stop of the profiler), and one not yet admitted.
REQUESTS = ([_request(i, queued=i + 0.001,
                      admitted=i + 0.001 + 0.001 * (i + 1),
                      first=i + 0.001 + 0.001 * (i + 1) + 0.05 * (i + 1))
             for i in range(10)]
            + [_request(10, queued=9.9, admitted=19.0, first=19.5),
               _request(11, queued=9.95)])


@pytest.mark.parametrize("name,want", [
    ("queue_wait_p90_ms", np.percentile(np.arange(1, 11), 90)),
    ("prefill_p90_ms", np.percentile(50 * np.arange(1, 11), 90)),
])
def test_stamp_p90s(name, want):
    got = servespans.stamp_p90s(REQUESTS, end=10.0)[name]
    assert got == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", ["queue_wait_p90_ms", "prefill_p90_ms"])
def test_stamp_p90s_read_nothing_without_stamps(name):
    unstamped = [_request(i) for i in range(4)]
    assert servespans.stamp_p90s(unstamped, end=10.0)[name] is None


def test_host_gap_reads_idle_per_engine_step():
    att = servespans.Attribution(
        window_s=1.0, idle_s=0.1, chips=1, idle_by_label={}, idle_gaps=[],
        spans={"serve.step": servespans.SpanTime(count=4, total_s=0.4,
                                                 idle_s=0.02),
               "serve.sync": servespans.SpanTime(count=4, total_s=0.3,
                                                 idle_s=0.004)})
    assert servespans.host_gap_ms(att) == pytest.approx(5.0)
    att.spans = {}
    assert servespans.host_gap_ms(att) is None


def test_recording_engine_keeps_requests_and_passes_the_rest():
    class Engine:
        pending = 3

        def add_request(self, req):
            return req.uid

    eng = servespans.Recording(Engine())
    reqs = [_request(i) for i in range(3)]
    assert [eng.add_request(r) for r in reqs] == [0, 1, 2]
    assert eng.requests == reqs and eng.pending == 3


# ---------------------------------------------------------------- CPU runs

def _fast_mix(cell, requests=48):
    # The smoke model steps far faster than the chip serves the real one:
    # offer more load so that requests queue and share the batch.
    return dict(cell.mix, requests=requests,
                arrival={"kind": "poisson", "rate_per_s": 40.0})


def test_engine_stamps_agree_with_the_derived_timeline():
    """The engine's own stamps of every finished request bracket the step
    that the timeline derives as its first-token step."""
    cell = harness.load_cell(ROOT, "granite-moe.short-poisson")
    harness.start(cell, require_tpu=False)
    program = harness.Program(cell, smoke=True)
    program.load(seed=5)
    eng = servespans.Recording(program.eng)
    drv = harness.Driver(eng, Traffic(_fast_mix(cell), program.arch.vocab,
                                      17), cell)
    drv.run(1.0, None)
    while eng.pending:                  # finish what was queued, add nothing
        drv.step()
    stamps = {r.uid: r for r in eng.requests}
    done = [r for r in drv.served.values() if r.done]
    assert len(done) >= 4
    for r in done:
        e = stamps[r.uid]
        first = drv.steps[r.first_step()]
        assert (r.due <= e.t_queued <= e.t_admitted <= e.t_first_token
                <= first.t_end)
        # The stamp falls inside that step, and inside no other.
        engine_first = [i for i, s in enumerate(drv.steps)
                        if s.t_start <= e.t_first_token <= s.t_end]
        assert engine_first == [r.first_step()]
        assert drv.steps[r.prefill_start(drv.chunk)].t_start <= e.t_admitted


def test_traced_window_holds_the_engine_spans(tmp_path, monkeypatch):
    """attribute() traces the engine's spans: every traced step's
    ``serve.step`` lies inside ``bench.traced`` with all its children, and
    the tracer is gone once the window ends."""
    from repro import obs
    load = harness.load_cell

    def fast(root, name):
        cell = load(root, name)
        cell.mix = _fast_mix(cell)
        return cell

    monkeypatch.setattr(harness, "load_cell", fast)
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.5)
    out = servespans.attribute("granite-moe.short-poisson", 5, 1.5,
                               str(tmp_path), root=ROOT, require_tpu=False,
                               smoke=True)
    assert obs.get_tracer() is None
    spans = servespans.host_spans(ProfileData.from_file(
        devtrace.find_xplane(str(tmp_path))))
    (lo, hi), = [(a, b) for a, b, n in spans if n == devtrace.TRACED_SPAN]
    steps = [(a, b) for a, b, n in spans if n == "serve.step"]
    assert len(steps) == out["traced_steps"] > 0
    assert all(lo <= a and b <= hi for a, b in steps)
    assert {"serve.admit", "serve.schedule", "serve.dispatch", "serve.sync",
            "serve.commit", "serve.telemetry"} <= {n for _, _, n in spans}
    # The stamps of the requests whose first token came in the span.
    assert 0 <= out["queue_wait_p90_ms"] <= out["ttft_p90_ms"]
    assert 0 < out["prefill_p90_ms"] <= out["ttft_p90_ms"]
