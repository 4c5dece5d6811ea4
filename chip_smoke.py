"""Chip smoke test: serve the benchmark models at published width on a TPU.

    python chip_smoke.py              # one chip: granite-moe-1b-a400m, phi3-mini-3.8b
    python chip_smoke.py --chips 4    # four chips: the sharded GEMMs only

One chip.  Each model is built by ``launch/serve.py``'s own loader and
engine (``ServeEngine`` -> ``LM.paged_step`` -> ``mp_dot`` /
``mp_dot_grouped`` -> the Pallas MPGEMM kernel, plus paged flash
attention) and serves a few requests on the Pallas backend.  The same
requests then run on the XLA backend on the same chip, and the first
prefill step's logits of the two backends are compared.  Weights are random
from ``PRNGKey(0)``; prompts come from ``--seed``.

Four chips (``--chips 4``).  ``mp_dot_sharded`` (column, row/ring and
gather/ring) on a Table III row, and ``mp_dot_grouped_sharded`` with
granite's 32 experts split 8 per chip, each against single-device
``mp_dot`` on device 0.

Every phase must pass.  The last line of stdout is one JSON object naming
the device, printed only when all phases passed.  Without a TPU the script
exits non-zero before computing anything; it never falls back to the CPU.
JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``.
"""
import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Serving shape: the continuous engine at max_batch 4, max_len 512, 16-token
# pages.  Prompt lengths are multiples of the prefill chunk, so the engine
# compiles exactly two step shapes (chunk CHUNK and decode).
MAX_BATCH, MAX_LEN, PAGE, CHUNK, MAX_NEW = 4, 512, 16, 64, 16
PROMPT_LENGTHS = (64, 128, 192, 256)
N_REQUESTS = 6
# First-prefill-step logits, Pallas vs XLA: RMS of the difference over the
# RMS of the XLA logits.
LOGITS_BAND = 2e-2
# Sharded GEMMs vs single-device mp_dot (bf16 outputs).
GEMM_BAND = 1e-2
SERVE_MODELS = (("granite-moe-1b-a400m", "bf16"),
                ("phi3-mini-3.8b", "bf16_serve"))


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(got, want):
    """(max |got - want|, RMS(got - want) / RMS(want)) in f32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = got - want
    rms = float(np.sqrt(np.mean(want * want)))
    return float(np.max(np.abs(diff))), float(
        np.sqrt(np.mean(diff * diff)) / max(rms, 1e-30))


def tpu_devices(n):
    """The first ``n`` TPU devices; refuses any other platform."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r}); this smoke "
          f"test runs on the chip only")
    check(len(devs) >= n, f"need {n} TPU devices, JAX sees {len(devs)}")
    return devs


def first_step_inputs(prompts, bt_width):
    """The engine's first step: MAX_BATCH fresh requests, CHUNK prompt
    tokens each, every row on its own pages (page 0 is scratch)."""
    import jax.numpy as jnp
    tokens = np.stack([p[:CHUNK] for p in prompts[:MAX_BATCH]])
    bt = 1 + np.arange(MAX_BATCH * bt_width, dtype=np.int32).reshape(
        MAX_BATCH, bt_width)
    q_start = np.zeros((MAX_BATCH,), np.int32)
    n_valid = np.full((MAX_BATCH,), CHUNK, np.int32)
    return tuple(jnp.asarray(a) for a in (tokens, bt, q_start, n_valid))


def serve_on(backend, args, model, params, prompts):
    """One backend's pass: compile and run the first prefill step, then
    serve every request through the engine."""
    import jax
    from repro.core import config
    from repro.launch import serve
    from repro.serve.engine import Request

    # Traces are keyed on the function, not on the backend selected at
    # trace time: start each backend from empty in-memory caches.
    jax.clear_caches()
    with config.gemm_backend(backend):
        check(config.get_gemm_backend() == backend,
              f"backend selection returned {config.get_gemm_backend()!r}")
        eng = serve.make_engine(args, model, params)
        tokens, bt, q_start, n_valid = first_step_inputs(prompts,
                                                         eng.bt_width)
        t0 = time.perf_counter()
        step = jax.jit(model.paged_step).lower(
            params, tokens, eng.caches, bt, q_start, n_valid).compile()
        compile_s = time.perf_counter() - t0
        kernels = step.as_text().count("tpu_custom_call")
        logits, _ = step(params, tokens, eng.caches, bt, q_start, n_valid)
        logits = np.asarray(logits[:, :model.cfg.vocab], np.float32)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        out = eng.generate(reqs)
        wall_s = time.perf_counter() - t0
        steps = len(eng.step_telemetry)
    del eng, step
    return dict(compile_s=compile_s, kernels=kernels, logits=logits,
                out=out, wall_s=wall_s, steps=steps)


def serve_phase(arch, policy, seed):
    import jax
    from repro.launch import serve

    args = serve.parse_args([
        "--arch", arch, "--policy", policy,
        "--max-batch", str(MAX_BATCH), "--max-len", str(MAX_LEN),
        "--page-size", str(PAGE), "--prefill-chunk", str(CHUNK),
        "--max-new", str(MAX_NEW)])
    t0 = time.perf_counter()
    cfg, model, params = serve.load_model(args)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    leaves = jax.tree_util.tree_leaves(params)
    n_params = sum(x.size for x in leaves)
    n_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    say(f"{arch}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} experts={cfg.n_experts}/top-{cfg.experts_per_token}"
        f" vocab={cfg.vocab}; policy={policy}; {n_params} params, "
        f"{n_bytes} bytes ({'/'.join(dtypes)}), init {init_s:.1f} s")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab, (int(n),)).astype(np.int32)
               for n in rng.choice(PROMPT_LENGTHS, N_REQUESTS)]
    runs = {b: serve_on(b, args, model, params, prompts)
            for b in ("pallas", "xla")}
    pal, xla = runs["pallas"], runs["xla"]

    for name, r in runs.items():
        n_tok = sum(len(v) for v in r["out"].values())
        say(f"{arch} [{name}]: step compile {r['compile_s']:.1f} s, "
            f"{r['kernels']} tpu_custom_call in the compiled prefill step; "
            f"served {len(r['out'])} requests, {n_tok} tokens in "
            f"{r['steps']} steps, chip wall time {r['wall_s']:.2f} s")
        check(len(r["out"]) == N_REQUESTS
              and all(len(v) for v in r["out"].values()),
              f"{arch} [{name}]: not every request produced tokens")
        check(all(0 <= t < cfg.vocab for v in r["out"].values() for t in v),
              f"{arch} [{name}]: token id outside the vocabulary")
        check(np.isfinite(r["logits"]).all(),
              f"{arch} [{name}]: non-finite first-step logits")
    check(pal["kernels"] > 0, f"{arch}: the Pallas step holds no kernel")
    check(xla["kernels"] == 0,
          f"{arch}: the XLA step holds {xla['kernels']} Pallas kernels")

    max_abs, rel = rel_err(pal["logits"], xla["logits"])
    agree = total = 0
    for uid, toks in xla["out"].items():
        mine = pal["out"][uid]
        total += max(len(toks), len(mine))
        agree += sum(a == b for a, b in zip(mine, toks))
    stats = jax.devices()[0].memory_stats() or {}
    say(f"{arch}: first-step logits Pallas vs XLA: max abs {max_abs:.4g}, "
        f"RMS err / RMS {rel:.4g} (band {LOGITS_BAND}); greedy tokens "
        f"agree {agree}/{total}; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'n/a')}")
    check(rel <= LOGITS_BAND,
          f"{arch}: logits error {rel:.4g} outside the band {LOGITS_BAND}")


def sharded_phase(seed):
    import jax
    import jax.numpy as jnp
    from benchmarks.common import MOE_GROUPED_WORKLOADS, PAPER_WORKLOADS
    from repro.core.gemm import mp_dot, mp_dot_grouped
    from repro.distributed.shard_gemm import (
        mp_dot_grouped_sharded, mp_dot_sharded,
    )
    from repro.launch.mesh import make_tp_mesh

    dev0 = jax.devices()[0]
    mesh = make_tp_mesh(4)
    kx, kb = jax.random.split(jax.random.PRNGKey(seed))

    def compare(name, f, ref_f, *operands):
        want = jax.jit(ref_f)(*(jax.device_put(o, dev0) for o in operands))
        compiled = jax.jit(f).lower(*operands).compile()
        got = compiled(*operands)
        n_dev = len(got.sharding.device_set)
        kernels = compiled.as_text().count("tpu_custom_call")
        max_abs, rel = rel_err(got, want)
        say(f"{name}: out {got.shape} {got.dtype} over {n_dev} devices, "
            f"{kernels} tpu_custom_call; vs mp_dot on device 0: max abs "
            f"{max_abs:.4g}, RMS err / RMS {rel:.4g} (band {GEMM_BAND})")
        check(n_dev == 4, f"{name}: output spans {n_dev} devices, not 4")
        check(kernels > 0, f"{name}: no Pallas kernel in the program")
        check(rel <= GEMM_BAND, f"{name}: error {rel:.4g} outside the band")

    row, m, n, k = PAPER_WORKLOADS[10]
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    b = (jax.random.normal(kb, (k, n)) * k ** -0.5).astype(jnp.bfloat16)
    for partition in ("column", "row", "gather"):
        compare(f"mp_dot_sharded Table III row {row} ({m}x{n}x{k}) "
                f"{partition}/ring",
                functools.partial(mp_dot_sharded, mesh=mesh,
                                  partition=partition, overlap="ring",
                                  policy="bf16"),
                functools.partial(mp_dot, policy="bf16"), x, b)

    name, g, m, n, k = MOE_GROUPED_WORKLOADS[2]
    x = jax.random.normal(kx, (g, m, k), jnp.bfloat16)
    b = (jax.random.normal(kb, (g, k, n)) * k ** -0.5).astype(jnp.bfloat16)
    compare(f"mp_dot_grouped_sharded {name} ({g} experts, {g // 4} per "
            f"chip, {m}x{n}x{k})",
            functools.partial(mp_dot_grouped_sharded, mesh=mesh,
                              policy="bf16"),
            functools.partial(mp_dot_grouped, policy="bf16"), x, b)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve the models on one chip; 4: only the "
                         "sharded GEMMs on a four-chip mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the prompts and GEMM operands")
    args = ap.parse_args(argv)

    try:
        devs = tpu_devices(args.chips)
        if os.environ.get("REPRO_GEMM_BACKEND"):
            raise SmokeFailure(
                "REPRO_GEMM_BACKEND is set; the smoke test selects the "
                "backend itself")
        sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
        import jax
        from repro import obs
        from repro.core import config

        cache_dir = config.use_compile_cache()
        cache_events = {}
        jax.monitoring.register_event_listener(
            lambda event, **kw: cache_events.__setitem__(
                event, cache_events.get(event, 0) + 1))
        registry = obs.MetricsRegistry()
        obs.set_registry(registry)

        d = devs[0]
        say(f"device {d.platform} {d.device_kind} x{len(devs)}; "
            f"jax {jax.__version__}; compile cache {cache_dir}")
        check(config.get_gemm_backend() == "pallas",
              f"auto backend resolved to {config.get_gemm_backend()!r}")

        if args.chips == 4:
            sharded_phase(args.seed)
        else:
            for arch, policy in SERVE_MODELS:
                serve_phase(arch, policy, args.seed)
                gc.collect()
                jax.clear_caches()
                live = sum(a.nbytes for a in jax.live_arrays())
                say(f"{arch}: freed; {live} bytes still live on the device")

        fallbacks = registry.counter("kernel_fallback_total").total()
        hits = cache_events.get("/jax/compilation_cache/cache_hits", 0)
        writes = cache_events.get("/jax/compilation_cache/cache_misses", 0)
        say(f"kernel_fallback_total {fallbacks:g}; compile cache: {hits} "
            f"hits, {writes} entries written")
        check(fallbacks == 0, "a kernel fell back to the XLA reference")
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
