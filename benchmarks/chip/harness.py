"""One run of one benchmark cell on the chip.

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; the harness finds everything else by
name: ``configs/<config>.json`` (the model's sizes and the repo arch id
and precision policy it is served with), ``traffic/<mix>.json`` (read by
``traffic.py``), ``cells/<cell>.json`` (engine settings and the limit of
the correctness check) and ``metrics/<metric>.py`` (one reader per
metric).

A run: build the model and engine through ``launch/serve.py``
(``make_engine``) with weights drawn on the device from the seed, warm
both step shapes (a prefill chunk and a decode step) with one request,
drive the mix through ``ServeEngine.step()`` for the window, read the
peak device memory, free the program's state, and compare the served
tokens of a seeded sample of finished requests with the float32
reference (``reference.py``).  With tracing on, the profiler records the
first ``TRACE_SECONDS`` of the window and the per-layer metrics are read
from that span.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import devtrace, timeline, work
from benchmarks.chip.traffic import Traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 10.0      # traced span at the start of the window
DRAIN_LIMIT_S = 60.0      # how long past the window a due request may take
WARM_UID = -1


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a GEMM backend other than Pallas."""


def say(msg: str) -> None:
    print(f"[chip-bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec

def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # configs/<config>.json
    mix: dict                 # traffic/<mix>.json
    engine: dict              # cells/<cell>.json "engine"
    check: dict               # cells/<cell>.json "check"
    end_to_end: List[dict]    # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def load_cell(root: str, name: str) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if _applies(m, name, e2e_names)]
    cellf = _load_json(os.path.join(HERE, "cells", f"{name}.json"))
    return Cell(name=name, chips=w["chips"],
                config=_load_json(os.path.join(root, conf["file"])),
                mix=_load_json(os.path.join(HERE, "traffic",
                                            f"{w['traffic']}.json")),
                engine=cellf["engine"], check=cellf["check"],
                end_to_end=e2e, per_layer=per)


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chip_bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# The configuration file's keys that must equal the served ArchConfig.
_SIZE_KEYS = (("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
              ("num_attention_heads", "n_heads"),
              ("num_key_value_heads", "n_kv_heads"),
              ("intermediate_size", "d_ff"), ("vocab_size", "vocab"),
              ("num_local_experts", "n_experts"),
              ("num_experts_per_tok", "experts_per_token"),
              ("rope_theta", "rope_theta"),
              ("tie_word_embeddings", "tie_embeddings"))


# Scalars the repo's model code fixes: (key, value it runs with).
def _fixed_scalars(arch):
    return (("rms_norm_eps", 1e-6),
            ("attention_multiplier", arch.head_dim ** -0.5),
            ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
            ("logits_scaling", 1.0))


def check_config(config: dict, arch, max_len: int) -> None:
    """Refuse a configuration file that differs from what the repo serves:
    sizes, the scalars its model code fixes, and a sliding window that a
    context of ``max_len`` could reach (the repo serves these models
    without one)."""
    bad = [(k, config.get(k, 0), getattr(arch, a)) for k, a in _SIZE_KEYS
           if config.get(k, 0) != getattr(arch, a)]
    bad += [(k, config[k], v) for k, v in _fixed_scalars(arch)
            if k in config and abs(config[k] - v) > 1e-12 * max(1.0, v)]
    if config.get("sliding_window") and config["sliding_window"] < max_len:
        bad.append(("sliding_window", config["sliding_window"], None))
    if bad:
        raise SystemExit(f"configuration file disagrees with the served "
                         f"{arch.name}: {bad}")


def seed_key(seed: int):
    """The weight key of a seed (any size of integer)."""
    import jax
    return jax.random.PRNGKey(int(np.random.SeedSequence(seed).generate_state(
        1, np.uint32)[0]))


# ------------------------------------------------------------------ window

@dataclasses.dataclass
class Window:
    t0: float
    deadline: float
    end: float
    n_steps: int                       # steps that started before deadline
    traced: Optional[range] = None     # steps inside the traced span
    late_s: List[float] = dataclasses.field(default_factory=list)


class Driver:
    """Feeds one mix into one engine and records every step."""

    def __init__(self, eng, traffic: Traffic, cell: Cell):
        self.eng = eng
        self.traffic = traffic
        self.chunk = cell.engine["prefill_chunk"]
        self.max_batch = cell.engine["max_batch"]
        self.steps: List[timeline.Step] = []
        self.served: Dict[int, timeline.Served] = {}
        self.next_i = 0
        self.t0 = 0.0

    def _add(self, due: Optional[float]) -> None:
        from repro.serve.engine import Request
        r = self.traffic.request(self.next_i)
        self.next_i += 1
        self.eng.add_request(Request(uid=r.uid, prompt=r.prompt,
                                     max_new_tokens=r.max_new))
        self.served[r.uid] = timeline.Served(
            uid=r.uid, prompt_len=len(r.prompt), max_new=r.max_new, due=due,
            added_step=len(self.steps))

    def feed(self, now: float) -> None:
        if self.traffic.kind == "backlog":
            while self.eng.pending < 2 * self.max_batch:
                self._add(None)
            return
        while (self.next_i < len(self.traffic)
               and self.t0 + self.traffic.due[self.next_i] <= now):
            self._add(self.t0 + self.traffic.due[self.next_i])

    def next_due(self) -> float:
        """When the next request arrives (never, once the open-loop mix
        has sent all of its requests)."""
        if self.next_i >= len(self.traffic):
            return math.inf
        return self.t0 + self.traffic.due[self.next_i]

    def step(self) -> None:
        ts = time.perf_counter()
        finished = self.eng.step()
        te = time.perf_counter()
        tel = self.eng.step_telemetry[-1]
        self.steps.append(timeline.Step(
            t_start=ts, t_end=te, phase=tel.phase, live=tel.live,
            tokens=tel.tokens, finished=len(finished),
            preemptions=tel.preemptions, page_occupancy=tel.page_occupancy,
            wall_s=tel.wall_s))
        idx = len(self.steps) - 1
        for req in finished:
            rec = self.served[req.uid]
            rec.finish_step = idx
            rec.out = list(req.out_tokens)
        if tel.preemptions:
            raise timeline.TimelineError(
                f"step {idx} preempted {tel.preemptions} requests; the "
                f"per-token timing assumes none")

    def run(self, seconds: float, trace_dir: Optional[str]) -> Window:
        import jax
        ann = jax.profiler.TraceAnnotation
        late: List[float] = []
        tracing = trace_dir is not None
        if tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = ann("bench.traced")
            traced.__enter__()
        self.t0 = time.perf_counter()
        deadline = self.t0 + seconds
        trace_end = self.t0 + min(TRACE_SECONDS, seconds)
        traced_steps = None
        while time.perf_counter() < deadline:
            with ann("bench.traffic"):
                self.feed(time.perf_counter())
            if self.eng.pending:
                with ann("bench.engine_step"):
                    self.step()
            else:
                wake = min(self.next_due(), deadline)
                with ann("bench.wait"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
                if wake < deadline:
                    late.append(time.perf_counter() - wake)
            if tracing and time.perf_counter() >= trace_end:
                traced.__exit__(None, None, None)
                jax.profiler.stop_trace()
                traced_steps = range(0, len(self.steps))
                tracing = False
        if tracing:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced_steps = range(0, len(self.steps))
        n = len(self.steps)
        end = max(deadline, self.steps[-1].t_end if n else deadline)
        return Window(t0=self.t0, deadline=deadline, end=end, n_steps=n,
                      traced=traced_steps, late_s=late)

    def drain(self, done, limit_s: float) -> bool:
        """Keep serving (arrivals included) until ``done()``; False if the
        limit passed first."""
        stop = time.perf_counter() + limit_s
        while not done():
            if time.perf_counter() > stop:
                return False
            self.feed(time.perf_counter())
            if self.eng.pending:
                self.step()
            else:
                time.sleep(max(0.0, min(self.next_due(), stop)
                               - time.perf_counter()))
        return True


# ------------------------------------------------------------------ check

def sample_requests(served: Dict[int, timeline.Served], k: int,
                    seed: int) -> List[timeline.Served]:
    """The longest finished request and a seeded sample of the others."""
    done = sorted((r for r in served.values() if r.done and r.out),
                  key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.out), -r.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 5])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def teacher_batch(sample: List[timeline.Served], traffic: Traffic,
                  max_len: int):
    """(tokens (K, max_len), positions (K, S), served (K, S), mask (K, S)):
    each row is a prompt followed by its served tokens; position j of a
    row predicts its j-th served token."""
    k = len(sample)
    s = max(len(r.out) for r in sample)
    tokens = np.zeros((k, max_len), np.int32)
    pos = np.zeros((k, s), np.int32)
    served = np.zeros((k, s), np.int32)
    mask = np.zeros((k, s), bool)
    for i, r in enumerate(sample):
        prompt = traffic.request(r.uid).prompt
        p, n = len(prompt), len(r.out)
        seq = np.concatenate([prompt, np.asarray(r.out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        pos[i, :n] = np.arange(p - 1, p - 1 + n)
        served[i, :n] = r.out
        mask[i, :n] = True
    return tokens, pos, served, mask


def reference_logits(config: dict, seed: int, tokens, pos):
    """The float32 reference's logits (K, S, vocab) at ``pos``."""
    from benchmarks.chip.reference import Reference
    return Reference(config, seed_key(seed)).logits_at(tokens, pos)


def gaps_of(logits, chosen, mask) -> np.ndarray:
    """Gap (in logit standard deviations) of each chosen token below the
    reference's best, over the masked positions."""
    import jax.numpy as jnp
    from benchmarks.chip.reference import gap_in_sd
    return np.asarray(gap_in_sd(logits, jnp.asarray(chosen)))[mask]


# Numbers a cell's check may compare: (name, limit key in the cell file,
# the statistic of the served tokens' gaps).
STATISTICS = (("widest_logit_gap_sd", "max_logit_gap_sd", np.max),
              ("mean_logit_gap_sd", "mean_logit_gap_sd", np.mean))


def compare(gaps: np.ndarray, check: dict) -> Dict[str, dict]:
    """The numbers ``correct`` rests on, each beside its limit: those of
    STATISTICS whose limit the cell file sets, and how many served tokens
    were checked (at least one)."""
    out = {name: {"value": float(stat(gaps)) if gaps.size else None,
                  "limit": float(check[key])}
           for name, key, stat in STATISTICS if key in check}
    out["served_tokens_checked"] = {"value": int(gaps.size), "limit": 1}
    return out


def verdict(checks: Dict[str, dict]) -> bool:
    """``correct``: at least one served token checked, and every compared
    number within its limit."""
    return checks["served_tokens_checked"]["value"] >= 1 and all(
        c["value"] <= c["limit"] for name, c in checks.items()
        if name != "served_tokens_checked")


# ------------------------------------------------------------------ run

@dataclasses.dataclass
class RunData:
    """What metric readers see (``metrics/<name>.py``: read(run))."""
    cell: Cell
    shape: work.Shape
    peaks: work.Peaks
    steps: List[timeline.Step]
    served: Dict[int, timeline.Served]
    window: Window
    setup_s: float
    trace: Optional[devtrace.Reduced] = None
    traced_work: Optional[Dict[str, work.Work]] = None
    model_flops: Optional[float] = None

    def window_steps(self) -> List[timeline.Step]:
        return self.steps[:self.window.n_steps]

    def layer_steps(self) -> List[timeline.Step]:
        """Steps the per-layer program counters are read over: the traced
        span in a traced run, else the window."""
        if self.window.traced is not None:
            return [self.steps[i] for i in self.window.traced]
        return self.window_steps()


def _traced_work(data: RunData, chunk: int) -> None:
    tr = data.window.traced
    rows = timeline.step_rows(data.served, chunk, tr.start, tr.stop)
    gemm, attn, flops = work.Work(), work.Work(), 0.0
    for i, r in zip(tr, rows):
        emitted = data.steps[i].tokens
        gemm += work.gemm_work(data.shape, r, emitted, data.peaks)
        attn += work.attention_work(data.shape, r, data.peaks)
        flops += work.model_flops(data.shape, r, emitted, data.peaks)
    data.traced_work = {"gemm": gemm, "attention": attn}
    data.model_flops = flops


def start(cell: Cell, require_tpu: bool):
    """Devices, compile cache and the in-window compile counter.

    -> (devices, compiles) where ``compiles["n"]`` counts XLA compilations.
    Raises NoChip without the TPU chips the cell needs or without the
    Pallas GEMM backend."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX sees "
                     f"{len(devices)} {dev.platform} device(s)")
    from repro.core import config as rconfig
    cache_dir = None
    if require_tpu:
        # Every program, however quick to compile, goes to the cache.
        cache_dir = rconfig.use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    backend = rconfig.get_gemm_backend()
    if require_tpu and backend != "pallas":
        raise NoChip(f"the GEMM backend resolves to {backend!r}, not pallas")
    compiles = {"n": 0}

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    say(f"{cell.name}: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"backend {backend}, compile cache {cache_dir}")
    return devices, compiles


class Program:
    """The system under test for one cell: the repo's model and its engine,
    built through ``launch/serve.py``, with weights drawn from a seed."""

    def __init__(self, cell: Cell, smoke: bool = False):
        from repro.configs import base as cb
        from repro.models.transformer import build_model
        self.cell = cell
        conf = cell.config
        self.arch = cb.get(conf["arch"], smoke=smoke)
        self.conf = smoke_config(conf, self.arch) if smoke else conf
        check_config(self.conf, self.arch, cell.engine["max_len"])
        self.model = build_model(self.arch, policy=self.conf["policy"],
                                 remat=False)
        self.params = self.eng = None

    def load(self, seed: int) -> None:
        """Weights from ``seed`` on the device, a fresh engine, and both
        step shapes of the window (a prefill chunk, a decode step) run
        once by a warm-up request of one chunk and two tokens."""
        from repro.launch import serve
        e = self.cell.engine
        self.params = self.model.init(seed_key(seed))
        args = serve.parse_args([
            "--arch", self.conf["arch"], "--policy", self.conf["policy"],
            "--max-batch", str(e["max_batch"]), "--max-len", str(e["max_len"]),
            "--page-size", str(e["page_size"]),
            "--max-pages", str(e["max_pages"]),
            "--prefill-chunk", str(e["prefill_chunk"])])
        self.eng = serve.make_engine(args, self.model, self.params)
        warm = np.random.default_rng(0).integers(
            2, self.arch.vocab, (e["prefill_chunk"],)).astype(np.int32)
        self.eng.add_request(warm, max_new_tokens=2, uid=WARM_UID)
        while self.eng.pending:
            self.eng.step()

    def free(self) -> None:
        self.eng = self.params = None
        gc.collect()


@dataclasses.dataclass
class Outcome:
    driver: Driver
    window: Window
    attempted: int
    failed: int
    explained: bool            # traced steps' work attributed to requests
    peak_bytes: int
    compiles: int              # XLA compilations inside the window
    trace_path: Optional[str] = None


def serve_window(program: Program, traffic: Traffic, seconds: float,
                 compiles: dict, trace_dir: Optional[str] = None) -> Outcome:
    """Drive the window, read the peak memory, then finish what the
    metrics need: requests due in an open-loop window, and the requests
    that worked in the traced steps."""
    import jax
    driver = Driver(program.eng, traffic, program.cell)
    before = compiles["n"]
    win = driver.run(seconds, trace_dir)
    in_window = compiles["n"] - before
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    if traffic.kind == "poisson":
        due = [r for r in driver.served.values() if r.due < win.deadline]
        if not driver.drain(lambda: all(r.done for r in due), DRAIN_LIMIT_S):
            say(f"requests due in the window still unfinished "
                f"{DRAIN_LIMIT_S:.0f} s after its close")
        attempted, failed = len(due), sum(not r.done for r in due)
    else:
        attempted = sum(r.added_step < win.n_steps
                        for r in driver.served.values())
        failed = 0
    explained = True
    if win.traced is not None:
        tr = win.traced
        explained = driver.drain(
            lambda: timeline.explained(driver.steps, driver.served,
                                       driver.chunk, tr.start, tr.stop),
            DRAIN_LIMIT_S)
    timeline.implied_counts(driver.steps, driver.served, driver.chunk)
    trace_path = devtrace.find_xplane(trace_dir) if trace_dir else None
    return Outcome(driver=driver, window=win, attempted=attempted,
                   failed=failed, explained=explained, peak_bytes=peak,
                   compiles=in_window, trace_path=trace_path)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str, require_tpu: bool = True,
        smoke: bool = False) -> dict:
    """One run; returns the result object the command prints last."""
    cell = load_cell(root, workload)
    devices, compiles = start(cell, require_tpu)
    dev = devices[0]
    program = Program(cell, smoke=smoke)
    program.load(seed)
    traffic = Traffic(cell.mix, program.arch.vocab, seed)
    peaks = work.load_peaks(dev.device_kind) if require_tpu else None
    setup_s = time.perf_counter() - t_start
    say(f"setup {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="chip-bench-trace-") if trace else None
    reduced = None
    try:
        out = serve_window(program, traffic, seconds, compiles, trace_dir)
        if trace_dir is not None:
            classes = {}
            for m in cell.per_layer:
                classes.update(getattr(load_reader(m["name"]), "KERNELS", {}))
            reduced = devtrace.reduce_file(out.trace_path, classes)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    say(f"compilations inside the window: {out.compiles}")
    served, steps, win = out.driver.served, out.driver.steps, out.window
    sample = sample_requests(served, cell.check["sample_requests"], seed)
    # Free the program's state before the reference runs on the chip.
    out.driver = None
    program.free()

    data = RunData(cell=cell, shape=work.Shape.from_config(program.conf),
                   peaks=peaks, steps=steps, served=served,
                   window=win, setup_s=setup_s, trace=reduced)
    if reduced is not None and out.explained and peaks is not None:
        _traced_work(data, cell.engine["prefill_chunk"])
    gaps = np.zeros((0,), np.float32)
    if sample:
        tokens, pos, chosen, mask = teacher_batch(sample, traffic,
                                                  cell.engine["max_len"])
        logits = reference_logits(program.conf, seed, tokens, pos)
        gaps = gaps_of(logits, chosen, mask)
    checks = compare(gaps, cell.check)
    correct = verdict(checks)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(m["name"]).read(data)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out.peak_bytes}
    result = {"correct": correct, "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in reduced.device_ops],
            "idle_gaps": [[n, t] for n, t in reduced.idle_gaps]}
    if win.late_s:
        say(f"generator lateness: max {max(win.late_s) * 1e3:.3f} ms over "
            f"{len(win.late_s)} idle waits")
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


def smoke_config(conf: dict, arch) -> dict:
    """The configuration file with the sizes of the repo's smoke preset."""
    out = dict(conf)
    for k, a in _SIZE_KEYS:
        out[k] = getattr(arch, a)
    if "attention_multiplier" in out:
        out["attention_multiplier"] = arch.head_dim ** -0.5
    return out
