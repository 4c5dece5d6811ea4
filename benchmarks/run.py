"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV, one function per paper table or
figure.  The numbers are modeled from the block planner, counted from
jaxprs, or CPU wall clocks; none is a device measurement.

Beyond the CSV, the harness owns the perf-trajectory artifacts
(docs/perf_trajectory.md):

  --emit            install a Recorder and write one versioned
                    ``BENCH_<area>.json`` per area to --out
  --diff DIR        compare the emitted files against the baselines in DIR
                    (benchmarks/baselines in CI); exit 1 on any regression
  --only AREA [...] run only the named areas (gemm / packing / quant /
                    sparse / serve / distributed / obs)
  --smoke           reduced workloads (small shapes, no wall clocks) — the
                    configuration the committed baselines are built from,
                    so ``--smoke --emit --diff benchmarks/baselines`` is
                    deterministic and CI-fast
"""
import argparse
import os
import sys

# Idempotent path setup: repo root (for `benchmarks.*`) and src/ (for
# `repro.*`), prepended once — re-imports and nested invocations must not
# grow sys.path.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

AREAS = ("gemm", "packing", "quant", "sparse", "serve", "distributed",
         "obs")


def run_gemm(smoke: bool = False) -> None:
    from benchmarks import (
        bench_autotune, bench_breakdown, bench_epilogue,
        bench_gemm_workloads, bench_irregular, bench_loads,
        bench_mixed_precision, bench_tiles, roofline_report,
    )
    bench_tiles.run()                      # paper Fig. 2
    bench_loads.run()                      # paper Fig. 3
    # paper Table III + Fig. 10/11 (+ Fig. 12 ladder, MoE expert shapes);
    # wall clocks are emit-noise, skip them under --smoke
    bench_gemm_workloads.run("float32", wall=not smoke)
    bench_gemm_workloads.run("bfloat16", wall=False)
    bench_gemm_workloads.run_grouped(wall=False)
    bench_irregular.run(check_kernel=not smoke)   # paper Fig. 13
    bench_mixed_precision.run()            # paper Fig. 14
    bench_breakdown.run()                  # paper Fig. 15
    roofline_report.run()                  # beyond-paper: dry-run roofline
    bench_autotune.run()                   # beyond-paper: Sec. III closed loop
    bench_epilogue.run(smoke=smoke)        # beyond-paper: fused epilogues
    bench_epilogue.run_trace_gate()
    if not smoke:
        bench_epilogue.run_wall_sanity()


def run_packing(smoke: bool = False) -> None:
    from benchmarks import bench_packing
    from benchmarks.common import MOE_GROUPED_WORKLOADS, PAPER_WORKLOADS
    # The emit path keeps the packed-zeros footprint small: 2-D workloads
    # from the paper's decode rows, grouped shapes from the small-expert
    # configs (granite / deepseek) — the mixtral packs are multi-GiB.
    work_2d = PAPER_WORKLOADS[:3] if smoke else None
    work_g = MOE_GROUPED_WORKLOADS[2:4] if smoke else None
    for policy in ("bf16", "int8"):        # beyond-paper: §IV-C AOT packing
        bench_packing.run(policy, work=work_2d)
        bench_packing.run_grouped(policy, work=work_g)
    bench_packing.run("bf16", trans_w=True, work=work_2d)
    if not smoke:
        bench_packing.run_wall_sanity()


def run_quant(smoke: bool = False) -> None:
    from benchmarks import bench_quant
    rows = bench_quant.run(smoke=smoke)   # precision-ladder weight traffic
    bench_quant.check_gate(rows)
    bench_quant.run_trace_gate(assert_gate=True)


def run_sparse(smoke: bool = False) -> None:
    from benchmarks import bench_sparse
    bench_sparse.run()                     # beyond-paper: tile-sparse MPGEMM
    bench_sparse.run_trace_gate(m_tokens=128 if smoke else 512)
    if not smoke:
        bench_sparse.run_wall()


def run_serve(smoke: bool = False) -> None:
    from benchmarks import bench_serve
    bench_serve.run()                      # beyond-paper: paged vs dense KV
    bench_serve.run_trace_gate(assert_gate=smoke)
    bench_serve.run_e2e(assert_gate=smoke)


def run_distributed(smoke: bool = False) -> None:
    from benchmarks import bench_distributed
    bench_distributed.run()                # beyond-paper: mesh scale-out
    # The collective-schedule gate re-execs under forced host devices when
    # the host has fewer than 4, so the emitted records are device-count
    # independent; the multi-device parity smoke runs only via
    # `bench_distributed --smoke` (the CI multidevice job).
    bench_distributed.run_trace_gate(assert_gate=smoke)


def run_obs(smoke: bool = False) -> None:
    from benchmarks import bench_obs
    # The transparency gate is exact (modeled payload bytewise-identical
    # with the registry/tracer on vs off), so it is always asserted; the
    # counter_inc wall timing is emit-noise, skipped under --smoke.
    bench_obs.run(smoke=smoke)


AREA_RUNNERS = {
    "gemm": run_gemm,
    "packing": run_packing,
    "quant": run_quant,
    "sparse": run_sparse,
    "serve": run_serve,
    "distributed": run_distributed,
    "obs": run_obs,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", nargs="+", choices=AREAS, default=None,
                    metavar="AREA",
                    help=f"run only these areas (default: all of {AREAS})")
    ap.add_argument("--emit", action="store_true",
                    help="record structured results and write "
                         "BENCH_<area>.json files to --out")
    ap.add_argument("--out", default=os.path.join(_ROOT, "bench_out"),
                    help="directory for emitted BENCH files "
                         "(default: <repo>/bench_out)")
    ap.add_argument("--diff", metavar="BASELINE_DIR", default=None,
                    help="after emitting, diff against the BENCH files in "
                         "this directory; exit 1 on regressions "
                         "(implies --emit)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads, no wall clocks (deterministic "
                         "— what the committed baselines use)")
    args = ap.parse_args(argv)
    if args.diff:
        args.emit = True

    areas = tuple(args.only) if args.only else AREAS

    recorder = None
    if args.emit:
        from benchmarks import common
        from repro.perf.trajectory import Recorder
        recorder = Recorder()
        common.set_recorder(recorder)
    try:
        for area in areas:
            AREA_RUNNERS[area](smoke=args.smoke)
    finally:
        if args.emit:
            from benchmarks import common
            common.set_recorder(None)

    if recorder is None:
        return 0

    paths = recorder.write_all(args.out)
    for area, path in sorted(paths.items()):
        print(f"bench_emit,{area},{path}")

    if not args.diff:
        return 0

    from repro.perf.diff import diff_paths, markdown_report
    from repro.perf.trajectory import bench_path
    results = []
    missing_emit = [a for a in areas if a not in paths]
    if missing_emit:
        print(f"bench_diff,ERROR,areas emitted no records: {missing_emit}")
        return 1
    for area in areas:
        baseline = bench_path(args.diff, area)
        if not baseline.exists():
            print(f"bench_diff,{area},no_baseline({baseline})")
            continue
        results.append(diff_paths(baseline, paths[area]))
    report = markdown_report(results)
    report_path = os.path.join(args.out, "bench_diff.md")
    with open(report_path, "w") as f:
        f.write(report)
    print(report)
    print(f"bench_diff_report,{report_path}")
    return 0 if all(r.ok for r in results) else 1


if __name__ == "__main__":
    from repro.core import config
    config.use_compile_cache()
    raise SystemExit(main())
