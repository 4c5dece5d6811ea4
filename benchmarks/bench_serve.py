"""Continuous-batching serve: modeled paged-vs-dense KV bytes, the
page-walk gate, and the end-to-end engine smoke.

Three measurement families (area ``serve``, -> ``BENCH_serve.json``):

  * ``serve_model_*``  — pure-arithmetic KV accounting per arch and request
                         mix: the dense wave engine holds ``max_batch x
                         max_len`` tokens of KV per layer regardless of the
                         actual lengths; the paged store holds
                         ``ceil(len/ps)*ps`` per request.  Deterministic —
                         the paged-vs-dense memory story the redesign ships.
  * ``serve_trace_*``  — the **page-walk gate**: the traced jaxpr of the
                         paged flash-attention launch has grid
                         ``(B, head groups, q blocks)`` at every block-table
                         width — the KV walk is a loop inside each program,
                         bounded by each row's length, so the table width
                         no longer multiplies the grid.  ``--smoke``
                         asserts that shape at widths 8, 4 and 2.
  * ``serve_e2e_*``    — a real continuous-batching run (smoke model):
                         short requests must retire strictly before a long
                         co-scheduled one (no head-of-line stall), the
                         paged KV footprint must undercut the dense
                         allocation at EVERY step, prefix sharing must
                         reuse full prompt pages, and the allocator
                         invariants must hold at exit.  Step counts and
                         tokens/s are run-dependent -> recorded as noisy.

``--smoke`` runs the hard gates and exits nonzero on any failure.  Set
``REPRO_SERVE_OUT`` to also write ``serve_report.md``.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, record
from repro.obs import audit
from repro.serve.kv_cache import cdiv

# (mix name, max_batch, max_len, page_size, request lengths at peak) —
# prompt+generated tokens held per live request, a decode-heavy snapshot.
SERVE_MIXES = [
    ("chat", 8, 2048, 16, (128, 384, 640, 896, 1152, 1408, 1664, 1920)),
    ("ragged", 8, 2048, 16, (64, 64, 96, 128, 160, 192, 224, 1984)),
    ("short", 8, 2048, 16, (48, 64, 80, 96, 112, 128, 144, 160)),
]

SERVE_ARCHS = ("phi3-mini-3.8b", "granite-moe-1b-a400m")


def _token_bytes(cfg, itemsize: int = 2) -> int:
    """Modeled KV bytes one token holds across all paged-attention layers
    (bf16 activations; mirrors serve/engine.py::_kv_token_bytes)."""
    from repro.models.transformer import PAGED_KINDS
    layers = sum(1 for kind in cfg.pattern if kind in PAGED_KINDS)
    return 2 * cfg.n_kv_heads * cfg.head_dim * itemsize * max(layers, 1)


def run(rows=None):
    """Modeled KV accounting: paged vs dense bytes per arch and mix."""
    from repro.configs import base as cb

    rows = rows if rows is not None else []
    for arch in SERVE_ARCHS:
        cfg = cb.get(arch)
        tb = _token_bytes(cfg)
        for mix, max_batch, max_len, ps, lengths in SERVE_MIXES:
            dense = max_batch * max_len * tb
            paged = sum(cdiv(n, ps) * ps for n in lengths) * tb
            saving = 1 - paged / dense
            rows.append(dict(arch=arch, mix=mix, token_bytes=tb,
                             kv_bytes_dense=dense, kv_bytes_paged=paged,
                             saving=saving))
            emit(f"serve_model_{arch}_{mix}", 0.0,
                 f"paged={paged};dense={dense};saving={saving:.2f};"
                 f"token_bytes={tb}")
            record(f"serve_model_{arch}_{mix}", "serve",
                   workload={"arch": arch, "max_batch": max_batch,
                             "max_len": max_len, "page_size": ps,
                             "lengths": list(lengths)},
                   metrics={"kv_bytes_dense": float(dense),
                            "kv_bytes_paged": float(paged),
                            "token_bytes": float(tb),
                            "kv_saving_frac": saving})
    return rows


def _traced_grid(b, hkv, g, tq, d, ps, width) -> tuple:
    """The pallas grid of a paged flash-attention launch (trace only)."""
    from repro.kernels.flash_attention import (kv_page_shape,
                                               paged_flash_attention)

    pages = kv_page_shape(1 + b * width, hkv, ps, d)
    args = (
        jax.ShapeDtypeStruct((b, hkv * g, tq, d), jnp.float32),
        jax.ShapeDtypeStruct(pages, jnp.float32),
        jax.ShapeDtypeStruct(pages, jnp.float32),
        jax.ShapeDtypeStruct((b, width), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
    )
    return audit.first_pallas_grid(audit.trace(
        lambda *a: paged_flash_attention(*a, interpret=True), *args))


def run_trace_gate(assert_gate: bool = False):
    """The jaxpr proof that the block table no longer shapes the launch:
    the grid is (B, head groups, query blocks) at every table width; the
    page walk runs inside each program, as far as each row's length."""
    from repro.kernels.flash_attention import kv_page_shape

    b, hkv, g, tq, d, ps = 2, 2, 2, 8, 64, 8
    groups = kv_page_shape(1, hkv, ps, d)[1]
    want = (b, groups, 1)       # hb*G*Tq = 32 query rows fit one block
    grids = {}
    for width in (8, 4, 2):
        grid = _traced_grid(b, hkv, g, tq, d, ps, width)
        grids[width] = grid
        emit(f"serve_trace_w{width}", 0.0,
             f"grid={grid};grid_steps={int(np.prod(grid))};"
             f"table_width={width}")
        record(f"serve_trace_w{width}", "serve", kind="trace",
               workload={"b": b, "hkv": hkv, "g": g, "tq": tq, "d": d,
                         "page_size": ps, "table_width": width},
               metrics={"grid_steps": float(int(np.prod(grid)))})
        if assert_gate:
            assert tuple(grid) == want, (
                f"traced grid {grid} at table width {width}, want {want}: "
                f"the launch is not (B, head groups, q blocks)")
    return grids


def run_e2e(assert_gate: bool = False):
    """Real continuous-batching smoke: no head-of-line stall, paged < dense
    KV at every step, prefix reuse, allocator invariants."""
    from repro.configs import base as cb
    from repro.models.transformer import build_model
    from repro.serve.engine import ServeEngine

    cfg = cb.get("phi3-mini-3.8b", smoke=True)
    model = build_model(cfg, policy="bf16", remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8)

    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab, (20,)).astype(np.int32)
    long_uid = eng.add_request(prompt, max_new_tokens=24)
    shorts = [eng.add_request(
        rng.integers(2, cfg.vocab, (6,)).astype(np.int32), max_new_tokens=3)
        for _ in range(2)]

    finish = {}
    step = 0
    while eng.pending:
        for req in eng.step():
            finish[req.uid] = step
        step += 1
        assert step < 300, "engine failed to drain"
    # Prefix sharing: re-serve the long prompt after its pages are indexed.
    eng.add_request(prompt, max_new_tokens=2)
    while eng.pending:
        eng.step()
    eng.kv.check_invariants()

    steps = eng.step_telemetry
    peak_pages = max(s.pages_in_use for s in steps)
    peak_kv = max(s.kv_bytes for s in steps)
    dense_kv = steps[0].kv_bytes_dense
    shared = eng.kv.stats.prefix_hit_tokens
    stall_gap = finish[long_uid] - max(finish[u] for u in shorts)
    emit("serve_e2e_smoke", 0.0,
         f"steps={len(steps)};peak_pages={peak_pages};peak_kv={peak_kv};"
         f"dense_kv={dense_kv};prefix_hit_tokens={shared};"
         f"stall_gap={stall_gap}")
    record("serve_e2e_smoke", "serve", kind="wall",
           workload={"arch": "phi3-mini-3.8b", "smoke": True, "max_len": 64,
                     "max_batch": 3, "page_size": 8},
           metrics={"kv_bytes_dense": float(dense_kv)},
           noisy={"steps": float(len(steps)),
                  "peak_pages": float(peak_pages),
                  "peak_kv_bytes": float(peak_kv),
                  "prefix_hit_tokens": float(shared),
                  "preemptions": float(sum(s.preemptions for s in steps)),
                  "stall_gap_steps": float(stall_gap)})
    if assert_gate:
        assert all(finish[u] < finish[long_uid] for u in shorts), (
            f"head-of-line stall: shorts finished at "
            f"{[finish[u] for u in shorts]}, long at {finish[long_uid]}")
        assert all(s.kv_bytes < s.kv_bytes_dense for s in steps), (
            "paged KV footprint did not undercut the dense allocation")
        assert shared >= eng.page_size, (
            f"prefix sharing reused only {shared} tokens")
    return dict(steps=len(steps), peak_pages=peak_pages, peak_kv=peak_kv,
                dense_kv=dense_kv, prefix_hit_tokens=shared,
                stall_gap=stall_gap)


def write_report(rows, grids, e2e, out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "serve_report.md")
    lines = [
        "# Continuous-batching serve: paged KV, end to end",
        "",
        "Modeled terms are pure KV accounting (dense wave allocation vs "
        "page-rounded actual lengths); grids are trace-time facts "
        "of the paged flash-attention launch; e2e numbers are one smoke "
        "run of the continuous engine on CPU.",
        "",
        "| arch | mix | paged KV | dense KV | saving |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['mix']} | {r['kv_bytes_paged']:,} "
            f"| {r['kv_bytes_dense']:,} | {r['saving']:.0%} |")
    lines += ["", "## Page-walk gate (traced grid)", ""]
    for width, grid in grids.items():
        lines.append(f"- table width {width}: grid {grid}")
    lines += [
        "",
        "## Engine smoke",
        "",
        f"- {e2e['steps']} steps; peak {e2e['peak_pages']} pages "
        f"({e2e['peak_kv']:,} B vs {e2e['dense_kv']:,} B dense)",
        f"- {e2e['prefix_hit_tokens']} prompt tokens prefix-shared",
        f"- short requests retired {e2e['stall_gap']} steps before the "
        f"long co-scheduled request",
        "",
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines))
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="hard gates: the paged-attention grid is (B, "
                         "head groups, q blocks) at every table width, no "
                         "head-of-line stall, paged < dense KV every step "
                         "(CI gate)")
    args = ap.parse_args()

    rows = run()
    grids = run_trace_gate(assert_gate=args.smoke)
    e2e = run_e2e(assert_gate=args.smoke)

    out_dir = os.environ.get("REPRO_SERVE_OUT")
    if out_dir:
        print(f"report: {write_report(rows, grids, e2e, out_dir)}")


if __name__ == "__main__":
    main()
