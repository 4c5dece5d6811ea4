"""Serving entrypoint: continuous-batching generation with the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --smoke --requests 6 --policy int8

The default ``--engine continuous`` runs the paged-KV continuous-batching
engine (requests admitted/retired every step, chunked prefill, prefix
sharing); ``--engine wave`` keeps the legacy static-batch wave engine.
"""
import argparse
import os
import time
import warnings

import numpy as np

import jax

from repro.configs import base as cb
from repro.core import config
from repro.models.transformer import build_model
from repro.serve.engine import Request, ServeEngine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m",
                    choices=cb.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="bf16",
                    choices=["bf16", "bf16_serve", "int8"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "wave"],
                    help="continuous: paged-KV continuous batching "
                         "(admit/retire every step); wave: the legacy "
                         "static-batch wave engine")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="concurrent sequence slots (default 2)")
    ap.add_argument("--batch", type=int, default=None,
                    help="DEPRECATED alias for --max-batch (with the old "
                         "wave-engine default semantics; prefer --max-batch"
                         " and, if you want waves, --engine wave)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in tokens (continuous engine)")
    ap.add_argument("--max-pages", type=int, default=None,
                    help="KV pool size in pages incl. the scratch page "
                         "(default: dense-equivalent capacity; smaller "
                         "values exercise preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens prefilled per step (continuous "
                         "engine; default max(page_size, 8))")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--warm-prefix", type=int, default=0, metavar="N",
                    help="pre-populate the paged-KV prefix index with an "
                         "N-token synthetic system prompt before serving "
                         "(continuous engine only); every request then "
                         "prepends that prompt and shares its pages "
                         "instead of re-prefilling them")
    ap.add_argument("--pack", action="store_true",
                    help="pack static weights into kernel-native tile "
                         "layouts at load time (repro.packing; cache via "
                         "REPRO_PACK_CACHE)")
    ap.add_argument("--pack-format", default=None,
                    choices=["int8", "int4", "fp8"],
                    help="payload codec for --pack (the precision ladder): "
                         "int8 per-tile quantized, int4 nibble-packed "
                         "(halves weight HBM traffic), fp8 e4m3 scaled. "
                         "Default: the policy's payload dtype")
    ap.add_argument("--sparsity", type=float, default=0.0,
                    help="fraction of weight TILES to prune at load time "
                         "(repro.sparse tile-magnitude pruning; 0 = off). "
                         "The sparse MPGEMM path then skips pruned tiles "
                         "entirely — grid, DMA, and MACs all shrink")
    ap.add_argument("--sparsity-method", default="magnitude",
                    choices=["magnitude", "nm"],
                    help="tile sparsifier: global magnitude top-k per "
                         "operand, or structured N:M over k-tiles (N of "
                         "every 4 kept, N derived from --sparsity — the "
                         "level quantizes to multiples of 1/4)")
    ap.add_argument("--sparsity-blocks", type=int, nargs=2, default=None,
                    metavar=("BK", "BN"),
                    help="tile size of the sparsity lattice (default: the "
                         "block planner's choice — which for SMALL weights "
                         "can be one whole-matrix tile, making pruning "
                         "all-or-nothing; pass smaller blocks for finer "
                         "granularity)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable the fused gated-activation/residual "
                         "epilogues (core/gemm_spec.py) — the unfused A/B "
                         "baseline benchmarks/bench_epilogue.py measures")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the obs metrics registry over HTTP on this "
                         "port (/metrics Prometheus text, /metrics.json, "
                         "/trace Chrome trace; 0 = ephemeral port, printed "
                         "at startup); a summary snapshot prints at exit")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="record obs tracing spans (serve step phases, "
                         "GEMM plan/pack/launch legs) and write a "
                         "Perfetto/chrome://tracing trace.json to FILE")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    """Parse the CLI; folds the deprecated ``--batch`` into ``--max-batch``."""
    args = build_parser().parse_args(argv)
    if args.batch is not None:
        print("[serve] --batch is deprecated; use --max-batch "
              "(and --engine wave for the legacy wave engine)")
        if args.max_batch is None:
            args.max_batch = args.batch
    if args.max_batch is None:
        args.max_batch = 2
    return args


def load_model(args):
    """Config, model and (packed / tile-sparse) parameters for ``args``.

    -> (cfg, model, params).  ``chip_smoke.py`` builds its models here too.
    """
    if args.no_fuse:
        # Read lazily at trace time by models/layers.py via
        # core.config.fused_epilogues(), so setting it before build works.
        os.environ["REPRO_FUSED_EPILOGUE"] = "0"

    if not 0.0 <= args.sparsity < 1.0:
        raise SystemExit(f"--sparsity must be in [0, 1) — a fraction of "
                         f"tiles to prune, got {args.sparsity}")
    if args.pack and args.sparsity > 0:
        raise SystemExit("--pack and --sparsity are mutually exclusive "
                         "(a weight is stored packed-dense OR tile-sparse)")
    if args.pack_format is not None and not args.pack:
        raise SystemExit("--pack-format requires --pack")

    cfg = cb.get(args.arch, smoke=args.smoke)
    model = build_model(cfg, policy=args.policy, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    if args.pack:
        from repro.packing import pack_params, packed_param_bytes
        params = pack_params(params, policy=args.policy,
                             m_hint=args.max_batch * 32,
                             pack_format=args.pack_format)
        fmt = f", format={args.pack_format}" if args.pack_format else ""
        print(f"[serve] packed static weights: "
              f"{packed_param_bytes(params)/2**20:.1f} MiB payload{fmt}")
    if args.sparsity > 0:
        from repro.sparse import (
            sparse_param_bytes, sparse_param_density, sparsify_params,
        )
        # The N:M pattern keeps n_keep of every 4 k-tiles: the requested
        # prune level quantizes to the NEAREST multiple of 1/4 (4:4 == a
        # tiny request honestly rounds to "prune nothing", never silently
        # over-prunes).
        m_block = 4
        n_keep = max(1, round((1.0 - args.sparsity) * m_block))
        if args.sparsity_method == "nm":
            print(f"[serve] N:M sparsity: keeping {n_keep} of every "
                  f"{m_block} k-tiles (requested prune {args.sparsity:.2f}"
                  f" -> effective {1 - n_keep / m_block:.2f})")
        params = sparsify_params(params, density=1.0 - args.sparsity,
                                 method=args.sparsity_method,
                                 nm=(n_keep, m_block),
                                 blocks=args.sparsity_blocks,
                                 policy=args.policy,
                                 m_hint=args.max_batch * 32)
        density = sparse_param_density(params)
        print(f"[serve] tile-sparse static weights: "
              f"{sparse_param_bytes(params)/2**20:.1f} MiB payload, "
              f"tile density {density:.2f} ({args.sparsity_method})")
        if density > (1.0 - args.sparsity) + 0.1:
            print(f"[serve] WARNING: effective tile density {density:.2f} "
                  f"is well above the requested {1 - args.sparsity:.2f} — "
                  f"the planner's tile lattice is too coarse for these "
                  f"weight shapes (pruning is per whole tile). Pass "
                  f"--sparsity-blocks with smaller BK BN for finer "
                  f"granularity.")
    return cfg, model, params


def make_engine(args, model, params) -> ServeEngine:
    """The serving engine ``args`` selects over ``model``/``params``."""
    if args.engine == "wave":
        with warnings.catch_warnings():
            # The CLI chose the wave engine explicitly; the constructor's
            # deprecation warning targets programmatic batch_size= callers.
            warnings.simplefilter("ignore", DeprecationWarning)
            eng = ServeEngine(model, params, batch_size=args.max_batch,
                              max_len=args.max_len)
    else:
        eng = ServeEngine(model, params, max_len=args.max_len,
                          max_batch=args.max_batch, page_size=args.page_size,
                          max_pages=args.max_pages,
                          prefill_chunk=args.prefill_chunk)
    return eng


def main(argv=None):
    args = parse_args(argv)
    config.use_compile_cache()
    tracer = None
    if args.trace_out:
        from repro.obs import trace as obs_trace
        tracer = obs_trace.Tracer()
        obs_trace.set_tracer(tracer)
    server = None
    if args.metrics_port is not None:
        from repro.obs.server import start_metrics_server
        server = start_metrics_server(port=args.metrics_port)
        print(f"[serve] metrics server on {server.url} "
              f"(/metrics, /metrics.json, /trace)")

    cfg, model, params = load_model(args)
    eng = make_engine(args, model, params)
    rng = np.random.default_rng(0)
    warm = None
    if args.warm_prefix > 0:
        if args.engine == "wave":
            raise SystemExit("--warm-prefix requires --engine continuous "
                             "(prefix sharing lives in the paged KV cache)")
        if args.warm_prefix + 32 + args.max_new >= args.max_len:
            raise SystemExit(
                f"--warm-prefix {args.warm_prefix} leaves no room for "
                f"request tails under --max-len {args.max_len} — raise "
                f"--max-len")
        warm = rng.integers(2, cfg.vocab,
                            (args.warm_prefix,)).astype(np.int32)
        t_w = time.time()
        new_pages = eng.warm_prefixes([warm])
        print(f"[serve] warmed {new_pages} prefix pages from a "
              f"{args.warm_prefix}-token system prompt in "
              f"{time.time() - t_w:.1f}s")

    def _prompt():
        tail = rng.integers(2, cfg.vocab,
                            (int(rng.integers(4, 32)),)).astype(np.int32)
        return tail if warm is None else np.concatenate([warm, tail])

    reqs = [Request(uid=i, prompt=_prompt(), max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    out = eng.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(v) for v in out.values())
    dev = jax.devices()[0]
    print(f"[serve] {args.requests} requests, {n_tok} tokens, {dt:.1f}s "
          f"wall ({n_tok/dt:.1f} tok/s on {dev.platform} "
          f"{dev.device_kind}, policy={args.policy})")
    if args.engine == "wave":
        for t in eng.telemetry:
            print(f"  wave{t.wave}: {t.requests} reqs, {t.tokens} tok, "
                  f"{t.tokens_per_s:.1f} tok/s, occupancy "
                  f"{t.slot_occupancy:.2f}, queue {t.queue_depth}")
    else:
        steps = eng.step_telemetry
        peak_pages = max((s.pages_in_use for s in steps), default=0)
        peak_kv = max((s.kv_bytes for s in steps), default=0)
        dense_kv = steps[0].kv_bytes_dense if steps else 0
        preempt = sum(s.preemptions for s in steps)
        shared = steps[-1].prefix_hit_tokens if steps else 0
        print(f"  {len(steps)} steps "
              f"({sum(1 for s in steps if s.phase != 'decode')} with "
              f"prefill), peak {peak_pages} pages "
              f"({peak_kv/2**20:.2f} MiB KV vs {dense_kv/2**20:.2f} MiB "
              f"dense), {preempt} preemptions, {shared} prompt tokens "
              f"prefix-shared")
        for s in steps[-3:]:
            print(f"  step{s.step}: {s.phase}, live {s.live}, "
                  f"queue {s.queue_depth}, {s.tokens} tok, "
                  f"pages {s.pages_in_use} ({s.page_occupancy:.2f}), "
                  f"{s.tokens_per_s:.1f} tok/s")
    for uid in sorted(out):
        print(f"  req{uid}: {out[uid][:10]}")

    if server is not None:
        # Scrape our own endpoint so the snapshot below exercised the full
        # HTTP path, not just the in-process registry.
        import urllib.request
        with urllib.request.urlopen(server.url + "/metrics") as resp:
            text = resp.read().decode()
        series = [ln for ln in text.splitlines()
                  if ln and not ln.startswith("#")]
        print(f"[serve] /metrics snapshot: {len(series)} series")
        for ln in series:
            if ln.startswith(("gemm_launches_total", "plan_cache_",
                              "paged_kv_", "serve_steps_total",
                              "serve_tokens_total")):
                print(f"  {ln}")
        server.close()
    if tracer is not None:
        tracer.export(args.trace_out)
        print(f"[serve] wrote {len(tracer)} trace events to "
              f"{args.trace_out}")


if __name__ == "__main__":
    main()
