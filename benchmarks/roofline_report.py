"""Aggregate experiments/dryrun JSONs into a roofline table (markdown) and
a CSV."""
import json
import os

from benchmarks.common import record

DRYRUN_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "experiments", "dryrun"))


def load(mesh="single"):
    rows = []
    if not os.path.isdir(DRYRUN_DIR):
        return rows
    for fname in sorted(os.listdir(DRYRUN_DIR)):
        if not fname.endswith(".json"):
            continue
        with open(os.path.join(DRYRUN_DIR, fname)) as f:
            r = json.load(f)
        if r.get("mesh") == mesh:
            rows.append(r)
    return rows


def markdown_table(mesh="single"):
    lines = [
        "| arch | shape | compute_s | memory_s | collective_s | bottleneck "
        "| useful | mem/dev GiB | note |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    for r in sorted(load(mesh), key=lambda r: (r["arch"],
                                               order.get(r["shape"], 9))):
        if r["status"] == "skip":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                         f"| — | SKIP: {r['reason'][:40]} |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                         f"| — | ERROR |")
            continue
        ro = r["roofline"]
        mem = r["memory"].get("peak_bytes_est", 0) / 2 ** 30
        lines.append(
            f"| {r['arch']} | {r['shape']} | {ro['compute_s']:.4g} "
            f"| {ro['memory_s']:.4g} | {ro['collective_s']:.4g} "
            f"| **{ro['bottleneck']}** | {ro['useful_ratio']:.3f} "
            f"| {mem:.2f} | mb={r.get('microbatches','-')} |")
    return "\n".join(lines)


def run():
    for mesh in ("single", "multi"):
        rows = load(mesh)
        ok = sum(1 for r in rows if r["status"] == "ok")
        skip = sum(1 for r in rows if r["status"] == "skip")
        err = len(rows) - ok - skip
        print(f"roofline_report_{mesh},0.00,cells={len(rows)};ok={ok};"
              f"skip={skip};error={err}")
        record(f"roofline_report_{mesh}", "gemm", kind="report",
               workload={"mesh": mesh},
               metrics={"cells": float(len(rows)), "cells_ok": float(ok),
                        "cells_skip": float(skip),
                        "cells_error": float(err)})


if __name__ == "__main__":
    print(markdown_table("single"))
    print()
    print(markdown_table("multi"))
