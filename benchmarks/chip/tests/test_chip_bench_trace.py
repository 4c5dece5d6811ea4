"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on a synthetic trace with known answers, and on a trace recorded on
a TPU v5e by the harness."""
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from benchmarks.chip import devtrace  # noqa: E402

SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 10500000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "gemm_kernel.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  event_metadata { key: 4 value { id: 4 name: "%while.5 = (s32[]) while((s32[]) %t), body=%b" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 5500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.engine_step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } } }
'''


def test_reduction_of_a_synthetic_trace():
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    r = devtrace.reduce(pd, {"gemm": r"^gemm_kernel", "none": r"^nothing"})
    # Span [1000, 11000] ns; ops [2000, 4000], [3000, 5000], [7000, 8000]
    # and [10500, 12500] clipped to [10500, 11000]; the while loop
    # [1500, 5500] spans ops of its body: busy 4000 + 1000 + 500 ns, and
    # it is no op of its own.
    assert r.chips == 1
    assert r.window_s == pytest.approx(10e-6)
    assert r.busy_s == pytest.approx(5.5e-6)
    assert r.waiting_s == pytest.approx(5.5e-6)   # bench.wait [5500, 11000]
    assert r.kernel_s == {"gemm": pytest.approx(2.5e-6), "none": 0.0}
    assert r.device_ops == [("fusion.1", pytest.approx(3e-6)),
                            ("gemm_kernel.3 [gemm]", pytest.approx(2.5e-6))]
    # Gaps: [1000, 1500] before any step, [5500, 7000] and [8000, 10500]
    # in wait.
    assert r.idle_gaps == [("bench.wait", pytest.approx(2.5e-6)),
                           ("bench.wait", pytest.approx(1.5e-6)),
                           ("outside harness spans", pytest.approx(0.5e-6))]


def test_no_traced_span_gives_nothing():
    txt = SYNTHETIC.replace('"bench.traced"', '"bench.other"')
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))
    assert devtrace.reduce(pd, {}) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "granite-moe.short-poisson.3steps.xplane.pb.gz")


def test_reduction_of_a_recorded_chip_trace():
    """Three engine steps of granite-moe.short-poisson traced by the
    harness on a TPU v5e (cut from a 10 s trace to the first three steps;
    host threads other than the harness's and op stats removed)."""
    import gzip
    from benchmarks.chip import harness
    with gzip.open(RECORDED, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    classes = {}
    for name in ("mpgemm_roofline", "paged_attention_roofline"):
        classes.update(harness.load_reader(name).KERNELS)
    r = devtrace.reduce(pd, classes)
    assert r.chips == 1
    assert 0.28 < r.window_s < 0.3
    assert 0 < r.busy_s < r.window_s
    # 24 layers: q, k, v, o, router, up, gate, down per layer and the head
    # are GEMM kernels; one paged attention kernel per layer.
    kinds = [n for n, _ in r.device_ops]
    assert any(n.endswith("[paged_attention]") for n in kinds)
    assert any(n.endswith("[mpgemm]") for n in kinds)
    assert r.kernel_s["paged_attention"] > r.kernel_s["mpgemm"] > 0
    assert r.kernel_s["paged_attention"] + r.kernel_s["mpgemm"] < r.busy_s
    assert not any("while" in n for n in kinds)
    assert len(r.idle_gaps) == devtrace.TOP
    assert all(g[0].startswith("bench.") or g[0] == "outside harness spans"
               for g in r.idle_gaps)
