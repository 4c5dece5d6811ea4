"""Shared benchmark utilities.

CPU wall-clock here is a *sanity signal only*, never a device time; the
other numbers are modeled roofline terms derived from the analytic planner
and the compiled dry-run artifacts.

Besides the CSV ``emit`` lines, every bench function reports its numbers
through :func:`record`, which forwards to the active
:class:`repro.perf.trajectory.Recorder` when the harness installed one
(``benchmarks/run.py --emit``) and is a no-op otherwise — standalone
``python benchmarks/bench_*.py`` runs stay print-only.
"""
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.constants import DEFAULT_HW

# Paper Table III: GEMM workloads from DeepSeek (1-18) and LLaMA (19-24).
PAPER_WORKLOADS = [
    (1, 64, 2112, 7168), (2, 64, 24576, 1536), (3, 64, 32768, 512),
    (4, 64, 7168, 16384), (5, 64, 4096, 7168), (6, 64, 7168, 2048),
    (7, 128, 2112, 7168), (8, 128, 24576, 1536), (9, 128, 32768, 512),
    (10, 128, 7168, 16384), (11, 128, 4096, 7168), (12, 128, 7168, 2048),
    (13, 4096, 2112, 7168), (14, 4096, 24576, 1536), (15, 4096, 32768, 512),
    (16, 4096, 7168, 16384), (17, 4096, 4096, 7168), (18, 4096, 7168, 2048),
    (19, 4096, 256, 4096), (20, 11008, 256, 4096), (21, 4096, 256, 11008),
    (22, 5120, 256, 5120), (23, 13824, 256, 5120), (24, 5120, 256, 13824),
]

# MoE expert grouped-GEMM workloads: (name, G experts, M tokens/expert, N, K).
# Shapes from the framework's own MoE configs (configs/mixtral_8x22b.py,
# configs/granite_moe_1b_a400m.py) at a 4k-token training step with top-k
# routing and capacity factor 1.25: M ≈ 1.25 * k * T / E.  These are the
# paper's DeepSeek/LLaMA serving shapes in their grouped (expert-batched)
# form — the workloads mp_dot_grouped exists for.
MOE_GROUPED_WORKLOADS = [
    ("mixtral-8x22b-up", 8, 1280, 16384, 6144),
    ("mixtral-8x22b-down", 8, 1280, 6144, 16384),
    ("granite-moe-up", 32, 1280, 512, 1024),
    ("granite-moe-down", 32, 1280, 1024, 512),
    ("deepseek-v2-lite-up", 64, 480, 1408, 2048),
    ("deepseek-v2-lite-down", 64, 480, 2048, 1408),
]


def wall_time_us(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def modeled_time_s(flops: float, bytes_: float, dtype: str = "bfloat16",
                   hw=DEFAULT_HW) -> float:
    peak = {"float32": hw.peak_flops_fp32, "bfloat16": hw.peak_flops_bf16,
            "int8": hw.peak_ops_int8}[dtype]
    return max(flops / peak, bytes_ / hw.hbm_bw)


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.2f},{derived}")


# --- structured-record plumbing ----------------------------------------------
# The harness (benchmarks/run.py --emit) installs a Recorder; bench modules
# call record(...) unconditionally and the call no-ops when none is active.

_RECORDER = None


def set_recorder(recorder) -> Optional[object]:
    """Install (or clear, with None) the active Recorder; returns the old."""
    global _RECORDER
    old, _RECORDER = _RECORDER, recorder
    return old


def get_recorder():
    return _RECORDER


def record(name: str, area: str, *, kind: str = "model", workload=None,
           metrics=None, noisy=None, plan=None, phases=None) -> None:
    """Report one structured benchmark result to the active Recorder.

    ``metrics`` are deterministic (modeled/traced — the diff gates them);
    ``noisy`` holds wall-clock numbers carried for trajectory plots but
    never compared.  No-op when no Recorder is installed, so bench modules
    can call this unconditionally.
    """
    if _RECORDER is None:
        return
    from repro.perf.metrics import WorkloadRecord
    _RECORDER.add(WorkloadRecord(
        name=name, area=area, kind=kind, workload=dict(workload or {}),
        metrics=dict(metrics or {}), noisy=dict(noisy or {}),
        plan=plan, phases=phases))


def record_plan(name: str, area: str, plan, *, source: str = "analytic",
                workload=None, metrics=None, noisy=None) -> None:
    """:func:`record` for a GemmPlan-backed number: the record auto-carries
    the plan's flops / hbm_bytes / cmr / tile_visits / modeled_us plus its
    blocking provenance.  No-op without an active Recorder."""
    if _RECORDER is None:
        return
    from repro.perf.metrics import record_from_plan
    _RECORDER.add(record_from_plan(
        name, area, plan, source=source, workload=workload,
        metrics=metrics, noisy=noisy))
