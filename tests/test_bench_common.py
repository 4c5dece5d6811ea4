"""Contracts of benchmarks/common.py: the wall-timer, the Table III
workload set, and the recorder plumbing every bench module calls."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common

# Paper Table III, verbatim: 18 DeepSeek shapes (IDs 1-18, M in
# {64, 128, 4096}) + 6 LLaMA shapes (IDs 19-24).  The benchmarks and the
# committed baselines all cite these 24 rows — a silent
# edit here would invalidate every downstream number, so the set is pinned
# exactly.
TABLE_III = [
    (1, 64, 2112, 7168), (2, 64, 24576, 1536), (3, 64, 32768, 512),
    (4, 64, 7168, 16384), (5, 64, 4096, 7168), (6, 64, 7168, 2048),
    (7, 128, 2112, 7168), (8, 128, 24576, 1536), (9, 128, 32768, 512),
    (10, 128, 7168, 16384), (11, 128, 4096, 7168), (12, 128, 7168, 2048),
    (13, 4096, 2112, 7168), (14, 4096, 24576, 1536), (15, 4096, 32768, 512),
    (16, 4096, 7168, 16384), (17, 4096, 4096, 7168), (18, 4096, 7168, 2048),
    (19, 4096, 256, 4096), (20, 11008, 256, 4096), (21, 4096, 256, 11008),
    (22, 5120, 256, 5120), (23, 13824, 256, 5120), (24, 5120, 256, 13824),
]


class TestPaperWorkloads:
    def test_exactly_table_iii(self):
        assert common.PAPER_WORKLOADS == TABLE_III

    def test_ids_are_1_to_24(self):
        assert [w[0] for w in common.PAPER_WORKLOADS] == list(range(1, 25))

    def test_moe_grouped_shapes_positive(self):
        for name, g, m, n, k in common.MOE_GROUPED_WORKLOADS:
            assert g > 1 and m > 0 and n > 0 and k > 0, name


class TestWallTimeUs:
    def test_warmup_and_iters_contract(self):
        calls = []

        def fn(x):
            calls.append(x)
            return x

        us = common.wall_time_us(fn, 1.0, iters=3, warmup=2)
        # warmup runs are excluded from timing but still executed
        assert len(calls) == 2 + 3
        assert us >= 0.0

    def test_returns_best_of_iters_in_us(self):
        import time
        t = iter([0.0, 1.0,      # iter 1: 1.0 s
                  1.0, 1.001,    # iter 2: 1 ms  <- best
                  1.001, 1.101])  # iter 3: 100 ms
        real = time.perf_counter
        time.perf_counter = lambda: next(t)
        try:
            us = common.wall_time_us(lambda: 0, iters=3, warmup=0)
        finally:
            time.perf_counter = real
        assert us == pytest.approx(1000.0)  # best iter, microseconds

    def test_zero_warmup_times_first_call(self):
        calls = []
        common.wall_time_us(lambda: calls.append(1), iters=1, warmup=0)
        assert len(calls) == 1


class TestRecorderPlumbing:
    def test_record_noops_without_recorder(self):
        assert common.get_recorder() is None
        # must not raise, must not require repro.perf to be imported
        common.record("x", "gemm", metrics={"a_us": 1.0})
        common.record_plan("y", "gemm", None)

    def test_set_recorder_routes_records(self):
        from repro.perf.trajectory import Recorder
        rec = Recorder()
        old = common.set_recorder(rec)
        try:
            common.record("w", "gemm", workload={"m": 1},
                          metrics={"a_us": 2.0}, noisy={"wall_us": 3.0})
            from repro.core.blocking import plan_gemm
            common.record_plan("p", "sparse", plan_gemm(64, 256, 512))
        finally:
            common.set_recorder(old)
        assert common.get_recorder() is old
        assert len(rec) == 2
        got = rec.records("gemm")[0]
        assert got.metrics == {"a_us": 2.0}
        assert got.noisy == {"wall_us": 3.0}
        assert rec.records("sparse")[0].metrics["flops"] == 2 * 64 * 256 * 512

    def test_invalid_record_raises_with_recorder(self):
        from repro.perf.trajectory import Recorder
        old = common.set_recorder(Recorder())
        try:
            with pytest.raises(ValueError):
                common.record("bad", "gemm", metrics={"x": "nan-string"})
        finally:
            common.set_recorder(old)
