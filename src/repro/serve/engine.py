"""Serving engine: continuous batching over a paged KV cache.

The engine admits and retires requests every decode step:

  * ``add_request()`` queues work; ``step()`` runs ONE forward — a pure
    decode step (chunk width 1) or, when a prompt is still being prefilled,
    a mixed chunked-prefill step where decode rows ride along with one
    valid column — and returns the requests that finished;
  * ``generate()`` is the compatibility wrapper: add everything, step until
    drained, return ``{uid: tokens}`` exactly like the old wave engine.

KV lives in fixed-size pages (``serve/kv_cache.py``): admission allocates
pages for the prompt (reusing prefix-shared pages), decode grows one page
at a time, and when the pool runs dry the most recently admitted request
is preempted (pages freed, request requeued for recompute) so older work
keeps flowing — no head-of-line blocking, O(actual-length) KV memory.

Every step emits a :class:`StepTelemetry` record (``engine.step_telemetry``
and the metrics registry).  The old per-wave records survive as an
aggregation: :class:`WaveTelemetry` is built FROM the step records by the
deprecated wave path (``batch_size=`` — a shim that keeps the original
left-padded static-batch loop for archs without a paged path and for
existing callers/tests).

With a tracer installed (``repro.obs``), each continuous step is one
``serve.step`` span over ``serve.admit``, ``serve.schedule`` (page
reservation, host batch arrays), ``serve.dispatch`` (uploads and the
jitted call, until it returns; ``pages_walked``: the live KV pages the
attention kernel walks per layer, beside the
``serve_attention_pages_walked_total`` / ``_table_slots_total``
counters; for MoE models ``serve_expert_rows_live_total``, the live
(token, expert) rows of each MoE layer, over
``serve_expert_rows_buffer_total``, the rows of its expert-sorted
buffer, both by phase), ``serve.sync`` (waiting for the chip and
copying the logits back), ``serve.commit`` (sampling, per-slot updates)
and ``serve.telemetry``.  Each :class:`Request` carries host-clock stamps
(``time.perf_counter()``) of its queueing, admission and first token.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Set

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.mpgemm import ragged_rows
from repro.serve.kv_cache import PagedKVCache, cdiv


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (T,) int32
    max_new_tokens: int = 16
    out_tokens: Optional[List[int]] = None
    # Host-clock stamps (time.perf_counter()) set by the engine.
    t_queued: Optional[float] = None       # add_request()
    t_admitted: Optional[float] = None     # last admission into a slot
    t_first_token: Optional[float] = None  # commit of the step sampling out[0]
    preemptions: int = 0


@dataclasses.dataclass(frozen=True)
class StepTelemetry:
    """Observability record for ONE engine step (or one wave phase).

    ``kv_bytes`` is the modeled KV footprint actually held (allocated pages
    x page bytes across layers); ``kv_bytes_dense`` is what the wave
    engine's per-slot max-length allocation would hold for the same batch
    width — the paged-vs-dense memory story per step.
    """

    step: int                # 0-based step index within this generate()/run
    phase: str               # "prefill" | "mixed" | "decode"
    live: int                # occupied slots doing useful work this step
    queue_depth: int         # requests waiting for a slot after this step
    tokens: int              # tokens emitted (sampled) this step
    preemptions: int         # requests preempted (requeued) this step
    pages_in_use: int        # KV pages held after this step
    page_occupancy: float    # pages_in_use / allocatable pages
    kv_bytes: int            # modeled bytes held in KV pages (all layers)
    kv_bytes_dense: int      # modeled bytes a dense max-len batch would hold
    prefix_hit_tokens: int   # cumulative prompt tokens skipped via sharing
    wall_s: float            # step wall time (incl. compile on first shapes)
    tokens_per_s: float      # tokens / wall_s


@dataclasses.dataclass(frozen=True)
class WaveTelemetry:
    """Aggregated observability for ONE wave of the deprecated wave engine.

    Since the continuous-batching redesign this is a thin aggregation over
    the per-step :class:`StepTelemetry` records (see :meth:`from_steps`);
    the fields and semantics are unchanged from the original per-wave
    implementation.  ``wall_s`` covers prefill + decode — and, for the
    FIRST wave after process start or a shape change, jax.jit compilation.
    ``prefill_s`` isolates the prefill(+compile) portion.
    """

    wave: int                # 0-based wave index within this generate() call
    requests: int            # requests admitted into the wave
    tokens: int              # tokens emitted by the wave
    decode_steps: int        # decode iterations the wave ran
    wall_s: float            # wave wall time (prefill + decode)
    prefill_s: float         # prefill wall time (incl. compile on wave 0)
    tokens_per_s: float      # tokens / wall_s
    slot_occupancy: float    # mean live-slot fraction over decode steps
    queue_depth: int         # requests still queued when the wave finished

    @classmethod
    def from_steps(cls, wave: int, requests: int, queue_depth: int,
                   steps: List[StepTelemetry], wall_s: float,
                   batch: int) -> "WaveTelemetry":
        """Fold one wave's StepTelemetry stream into the legacy record."""
        emits = [s for s in steps if s.phase != "prefill"]
        n_tok = sum(s.tokens for s in steps)
        prefill_s = sum(s.wall_s for s in steps if s.phase == "prefill")
        occ = (sum(s.live / batch for s in emits) / len(emits)
               if emits else 0.0)
        return cls(
            wave=wave, requests=requests, tokens=n_tok,
            decode_steps=sum(1 for s in emits if s.phase == "decode"),
            wall_s=wall_s, prefill_s=prefill_s,
            tokens_per_s=n_tok / wall_s if wall_s > 0 else 0.0,
            slot_occupancy=occ, queue_depth=queue_depth,
        )


@dataclasses.dataclass
class _Slot:
    req: Request
    length: int                  # tokens written into the KV pages
    pending: np.ndarray          # prompt tokens not yet prefilled
    next_token: Optional[int]    # sampled, not yet written (decode input)
    out: List[int]
    admitted: int                # admission order (preemption picks max)


def _kv_token_bytes(model) -> int:
    """Modeled KV bytes ONE token holds across all attention layers."""
    from repro.models.transformer import PAGED_KINDS
    cfg = model.cfg
    layers = sum(1 for k in cfg.pattern if k in PAGED_KINDS)
    itemsize = jnp.dtype(model.act_dtype).itemsize
    return 2 * cfg.n_kv_heads * cfg.head_dim * itemsize * max(layers, 1)


class ServeEngine:
    """Continuous-batching engine (paged KV).  The deprecated ``batch_size=``
    keyword selects the legacy wave engine (static batch, ring caches)."""

    def __init__(self, model, params, *, max_len: int,
                 max_batch: Optional[int] = None, page_size: int = 16,
                 max_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 eos_id: int = 1, greedy: bool = True,
                 on_wave: Optional[Callable[[WaveTelemetry], None]] = None,
                 batch_size: Optional[int] = None):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self.greedy = greedy
        self.on_wave = on_wave
        self.telemetry: List[WaveTelemetry] = []
        self.step_telemetry: List[StepTelemetry] = []
        self._token_bytes = _kv_token_bytes(model)
        self._wave_mode = batch_size is not None
        if self._wave_mode:
            obs.warn_deprecated(
                "serve_engine.batch_size",
                "ServeEngine(batch_size=) selects the deprecated wave "
                "engine; use max_batch= for continuous batching",
                stacklevel=2)
            self.batch_size = batch_size
            self._prefill = jax.jit(
                lambda p, b: model.prefill(p, b, max_len=max_len))
            self._decode = jax.jit(model.decode_step)
            return
        # ----------------------- continuous engine -----------------------
        reason = model.paged_unsupported_reason()
        if reason:
            raise ValueError(
                f"continuous batching unavailable: {reason} "
                f"(construct with batch_size= for the wave engine)")
        self.max_batch = max_batch if max_batch is not None else 8
        self.batch_size = self.max_batch   # observability-compat alias
        self.page_size = page_size
        self.bt_width = cdiv(max_len, page_size)
        # Default pool: dense-equivalent capacity (+ the scratch page), so
        # preemption only kicks in when the caller shrinks max_pages.
        self.max_pages = (max_pages if max_pages is not None
                          else self.max_batch * self.bt_width + 1)
        self.kv = PagedKVCache(self.max_pages, page_size)
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else max(page_size, 8))
        self.caches = model.init_paged_caches(self.max_pages, page_size)
        self._step_fn = jax.jit(model.paged_step)
        # top_k of the MoE layers (0: none) for the expert-row counters.
        self._moe_k = (model.cfg.experts_per_token
                       if "moe" in model.cfg.pattern else 0)
        self._chunks_dispatched: Set[int] = set()
        self._slots: List[Optional[_Slot]] = [None] * self.max_batch
        self._queue: deque = deque()
        self._admit_counter = 0
        self._step_counter = 0
        self._uid_counter = 0

    # ------------------------- continuous API ----------------------------

    def _require_continuous(self, what: str):
        if self._wave_mode:
            raise RuntimeError(
                f"{what} requires the continuous engine; this instance was "
                f"built with the deprecated batch_size= (wave) shim")

    def add_request(self, prompt, max_new_tokens: int = 16,
                    uid: Optional[int] = None) -> int:
        """Queue a request; returns its uid.  Also accepts a Request."""
        self._require_continuous("add_request()")
        if isinstance(prompt, Request):
            req = prompt
        else:
            if uid is None:
                uid = self._uid_counter
                self._uid_counter += 1
            req = Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                          max_new_tokens=max_new_tokens)
        self._uid_counter = max(self._uid_counter, req.uid + 1)
        req.t_queued = time.perf_counter()
        req.t_admitted = req.t_first_token = None
        req.preemptions = 0
        if len(req.prompt) >= self.max_len:
            raise ValueError(f"prompt length {len(req.prompt)} >= max_len "
                             f"{self.max_len}")
        worst = cdiv(min(len(req.prompt) + req.max_new_tokens, self.max_len),
                     self.page_size)
        if worst > self.max_pages - 1:
            raise ValueError(
                f"request {req.uid} needs up to {worst} pages, pool has "
                f"{self.max_pages - 1} allocatable — raise max_pages")
        self._queue.append(req)
        return req.uid

    def _admit(self) -> int:
        """FIFO admission into free slots while prompt pages fit; returns
        how many requests were admitted."""
        admitted = 0
        for i in range(self.max_batch):
            if not self._queue or self._slots[i] is not None:
                continue
            req = self._queue[0]
            shared_pages, shared_tokens = self.kv.match_prefix(req.prompt)
            self.kv.allocate(req.uid, shared_pages, shared_tokens)
            if not self.kv.ensure(req.uid, len(req.prompt)):
                self.kv.free_seq(req.uid)     # head doesn't fit; wait
                self.kv.rollback_prefix_hits(len(shared_pages), shared_tokens)
                break
            self._queue.popleft()
            req.t_admitted = time.perf_counter()
            self._slots[i] = _Slot(
                req=req, length=shared_tokens,
                pending=np.asarray(req.prompt[shared_tokens:], np.int32),
                next_token=None, out=[], admitted=self._admit_counter)
            self._admit_counter += 1
            admitted += 1
        return admitted

    def _evict_slot(self, i: int) -> None:
        """Preempt slot i: free its pages, requeue its request at the head
        (recompute semantics — generated tokens are discarded)."""
        s = self._slots[i]
        self.kv.free_seq(s.req.uid)
        s.req.preemptions += 1
        self._queue.appendleft(s.req)
        self._slots[i] = None
        self._preempted_now += 1
        obs.instant("serve.preempt", uid=s.req.uid, slot=i)

    def _reserve(self, slot: _Slot, n_new: int) -> bool:
        """Grow slot's table for n_new tokens, preempting newer requests
        under page pressure.  False if the slot itself got preempted (or
        already was: an earlier _reserve() this step may have evicted it,
        in which case its pages are gone and ensure() must not run)."""
        if not any(s is slot for s in self._slots):
            return False
        while not self.kv.ensure(slot.req.uid, slot.length + n_new):
            others = [i for i, s in enumerate(self._slots)
                      if s is not None and s is not slot]
            if others:
                j = max(others, key=lambda i: self._slots[i].admitted)
                if self._slots[j].admitted > slot.admitted:
                    self._evict_slot(j)
                    continue
            # slot is itself the newest — preempt it instead
            self._evict_slot(
                next(i for i, s in enumerate(self._slots) if s is slot))
            return False
        return True

    def step(self) -> List[Request]:
        """Run one engine step; returns the requests that finished."""
        self._require_continuous("step()")
        with obs.span("serve.step", step=self._step_counter):
            return self._step()

    def _step(self) -> List[Request]:
        t0 = time.perf_counter()
        self._preempted_now = 0
        with obs.span("serve.admit", step=self._step_counter,
                      queued=len(self._queue)):
            admitted = self._admit()
            obs.annotate(admitted=admitted)
        live = [s for s in self._slots if s is not None]
        if not live:
            if self._queue:
                raise RuntimeError(
                    "queued requests but nothing admitted — pool cannot "
                    "hold any queued prompt")
            return []
        # Chunk width: mixed prefill step if any prompt is still pending.
        chunk = max((min(self.prefill_chunk, len(s.pending))
                     for s in live if len(s.pending)), default=0)
        c = max(chunk, 1)
        phase = ("prefill" if chunk and all(s.next_token is None
                                            for s in live)
                 else "mixed" if chunk else "decode")
        with obs.span("serve.schedule"):
            tokens, q_start, n_valid, bt, walked = self._schedule(c, phase)
            obs.annotate(preempted=self._preempted_now)
        live = [s for s in self._slots if s is not None]
        obs.annotate(phase=phase, live=len(live), chunk=c)
        new_shape = c not in self._chunks_dispatched
        with obs.span("serve.dispatch", new_shape=new_shape,
                      pages_walked=walked):
            if new_shape:
                self._chunks_dispatched.add(c)
                obs.counter_inc("serve_new_step_shapes_total", chunk=c,
                                help="first dispatch of each step width "
                                     "(a compile or a compile-cache load)")
            logits, self.caches = self._step_fn(
                self.params, jnp.asarray(tokens), self.caches,
                jnp.asarray(bt), jnp.asarray(q_start), jnp.asarray(n_valid))
        with obs.span("serve.sync"):
            logits = np.asarray(logits)   # blocks until device done
        with obs.span("serve.commit"):
            finished, emitted = self._commit(logits, n_valid)
            obs.annotate(finished=len(finished))
        with obs.span("serve.telemetry"):
            wall = time.perf_counter() - t0
            pages = self.kv.pages_in_use
            rec = StepTelemetry(
                step=self._step_counter, phase=phase, live=len(live),
                queue_depth=len(self._queue), tokens=emitted,
                preemptions=self._preempted_now, pages_in_use=pages,
                page_occupancy=self.kv.occupancy,
                kv_bytes=pages * self.page_size * self._token_bytes,
                kv_bytes_dense=(self.max_batch * self.max_len
                                * self._token_bytes),
                prefix_hit_tokens=self.kv.stats.prefix_hit_tokens,
                wall_s=wall,
                tokens_per_s=emitted / wall if wall > 0 else 0.0)
            self._emit_step(rec)
        self._step_counter += 1
        return finished

    def _schedule(self, c: int, phase: str):
        """Reserve pages for this step's writes (may preempt) and build the
        host batch: (tokens (B, c), q_start (B,), n_valid (B,), block
        tables (B, W), the live pages the attention kernel walks in each
        layer).  _reserve() can evict any NEWER slot, so liveness is
        re-read from self._slots each iteration — a snapshot would hand
        slots whose pages were just freed back to _reserve()."""
        for i in range(self.max_batch):
            s = self._slots[i]
            if s is None:
                continue
            n_new = min(c, len(s.pending)) if len(s.pending) else 1
            self._reserve(s, n_new)
        if all(s is None for s in self._slots):
            raise RuntimeError("every live request was preempted — pool "
                               "cannot make progress")
        b = self.max_batch
        tokens = np.zeros((b, c), np.int32)
        q_start = np.zeros((b,), np.int32)
        n_valid = np.zeros((b,), np.int32)
        bt = np.zeros((b, self.bt_width), np.int32)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if len(s.pending):
                n = min(c, len(s.pending))
                tokens[i, :n] = s.pending[:n]
            else:
                n = 1
                tokens[i, 0] = s.next_token
            q_start[i] = s.length
            n_valid[i] = n
            bt[i] = self.kv.block_table_row(s.req.uid, self.bt_width)
        walked = int(cdiv(q_start + n_valid, self.page_size).sum())
        obs.counter_inc("serve_attention_pages_walked_total", amount=walked,
                        help="live KV pages the paged attention kernel "
                             "walks, per layer, summed over steps")
        obs.counter_inc("serve_attention_table_slots_total",
                        amount=b * self.bt_width,
                        help="block-table slots of the steps, per layer "
                             "(max_batch x table width)")
        if self._moe_k:
            obs.counter_inc("serve_expert_rows_live_total",
                            amount=int(n_valid.sum()) * self._moe_k,
                            phase=phase,
                            help="live (token, expert) rows of the MoE "
                                 "expert GEMMs, per MoE layer")
            obs.counter_inc("serve_expert_rows_buffer_total",
                            amount=ragged_rows(b * c * self._moe_k,
                                               self.model.cfg.n_experts),
                            phase=phase,
                            help="rows of the expert-sorted buffer, per "
                                 "MoE layer")
        return tokens, q_start, n_valid, bt, walked

    def _commit(self, logits: np.ndarray, n_valid: np.ndarray):
        """Sample each slot's token and advance it; -> (finished requests,
        tokens emitted)."""
        now = time.perf_counter()
        sampled = np.argmax(logits, axis=-1)
        finished: List[Request] = []
        emitted = 0
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            n = int(n_valid[i])
            s.length += n
            if len(s.pending):
                s.pending = s.pending[n:]
                if len(s.pending):
                    continue              # mid-prefill: logits unused
                self.kv.register_prefix(s.req.uid, s.req.prompt)
            tok = int(sampled[i])
            if not s.out:
                s.req.t_first_token = now
            s.out.append(tok)
            emitted += 1
            done = (tok == self.eos_id
                    or len(s.out) >= s.req.max_new_tokens
                    or s.length >= self.max_len - 1)
            if done:
                r = s.req
                r.out_tokens = list(s.out)
                finished.append(r)
                self.kv.free_seq(r.uid)
                self._slots[i] = None
                obs.observe("serve_queue_wait_seconds",
                            r.t_admitted - r.t_queued,
                            help="queued to last admission, per request")
                obs.observe("serve_prefill_seconds",
                            r.t_first_token - r.t_admitted,
                            help="last admission to first token, per "
                                 "request")
            else:
                s.next_token = tok
        return finished, emitted

    def _emit_step(self, rec: StepTelemetry) -> None:
        """One StepTelemetry record lands in both sinks: the in-memory
        stream and the process registry — serving, benches, and an HTTP
        scrape read the same numbers."""
        self.step_telemetry.append(rec)
        obs.counter_inc("serve_steps_total", phase=rec.phase,
                        help="engine steps by phase")
        if rec.tokens:
            obs.counter_inc("serve_tokens_total", amount=rec.tokens,
                            help="tokens sampled")
        if rec.preemptions:
            obs.counter_inc("serve_preemptions_total",
                            amount=rec.preemptions,
                            help="requests preempted under page pressure")
        obs.gauge_set("serve_queue_depth", rec.queue_depth)
        obs.gauge_set("serve_live_slots", rec.live)
        obs.gauge_set("serve_pages_in_use", rec.pages_in_use)
        obs.gauge_set("serve_page_occupancy", rec.page_occupancy)
        obs.gauge_set("serve_kv_bytes", rec.kv_bytes)
        obs.gauge_set("serve_prefix_hit_tokens", rec.prefix_hit_tokens)
        obs.observe("serve_step_wall_seconds", rec.wall_s, phase=rec.phase)

    @property
    def pending(self) -> int:
        """Requests not yet finished (queued + occupying a slot)."""
        return len(self._queue) + sum(s is not None for s in self._slots)

    def warm_prefixes(self, prompts,
                      *, max_tokens_each: Optional[int] = None) -> int:
        """Pre-populate the prefix-sharing index with system prompts.

        Runs each prompt through a throwaway 1-token request so its full
        prompt pages land in the refcounted prefix index BEFORE real
        traffic arrives — the first real request sharing that system
        prompt then prefills only its unshared tail instead of the whole
        prefix.  Prompts shorter than one page can never be indexed
        (sharing covers full pages only) and are skipped; longer ones are
        truncated to ``max_tokens_each`` and to what ``max_len`` admits.
        Resets the telemetry streams afterwards so warm-up steps never
        pollute serving observability.  Returns the number of newly
        indexed prefix pages.
        """
        self._require_continuous("warm_prefixes()")
        before = self.kv.prefix_entries
        budget = 0
        for prompt in prompts:
            toks = np.asarray(prompt, np.int32).reshape(-1)
            if max_tokens_each is not None:
                toks = toks[:max_tokens_each]
            toks = toks[: self.max_len - 2]
            if len(toks) < self.page_size:
                continue              # sharing covers full pages only
            self.add_request(toks, max_new_tokens=1)
            budget += 4 * (len(toks) + 1) + 64
        for _ in range(budget):
            if not self.pending:
                break
            self.step()
        if self.pending:
            raise RuntimeError(
                f"prefix warm-up failed to drain: {self.pending} warm "
                f"requests unfinished")
        self.step_telemetry = []
        self._step_counter = 0
        return self.kv.prefix_entries - before

    def generate(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Compatibility wrapper: run everything to completion.

        Resets the telemetry streams.  On the continuous engine this is
        add_request() + step()-until-drained; with the deprecated
        ``batch_size=`` shim it runs the legacy wave loop (identical
        behaviour and WaveTelemetry records to the pre-paging engine).
        """
        if self._wave_mode:
            return self._generate_waves(requests)
        self.step_telemetry = []
        self._step_counter = 0
        for r in requests:
            self.add_request(r)
        results: Dict[int, List[int]] = {}
        budget = sum(len(r.prompt) + r.max_new_tokens for r in requests)
        budget = 4 * budget + 64          # preemption/chunking slack
        for _ in range(budget):
            for req in self.step():
                results[req.uid] = list(req.out_tokens)
            if not self.pending:
                return results
        raise RuntimeError(f"generate() exceeded its step budget with "
                           f"{self.pending} requests unfinished")

    # ----------------- deprecated wave engine (batch_size=) --------------

    def _generate_waves(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Static-batch generation with slot reuse between waves."""
        results: Dict[int, List[int]] = {}
        queue = list(requests)
        self.telemetry = []
        self.step_telemetry = []
        self._wave_step = 0
        wave_idx = 0
        while queue:
            wave = queue[: self.batch_size]
            queue = queue[self.batch_size:]
            t0 = time.perf_counter()
            n_steps0 = len(self.step_telemetry)
            with obs.span("serve.wave", wave=wave_idx, requests=len(wave)):
                out = self._run_wave(wave, len(queue))
            wall = time.perf_counter() - t0
            record = WaveTelemetry.from_steps(
                wave_idx, len(wave), len(queue),
                self.step_telemetry[n_steps0:], wall, self.batch_size)
            self.telemetry.append(record)
            obs.counter_inc("serve_waves_total",
                            help="waves run by the deprecated wave engine")
            if self.on_wave is not None:
                self.on_wave(record)
            results.update(out)
            wave_idx += 1
        return results

    def _wave_record(self, phase: str, live: int, queue_depth: int,
                     tokens: int, wall: float) -> None:
        dense = self.batch_size * self.max_len * self._token_bytes
        rec = StepTelemetry(
            step=self._wave_step, phase=phase, live=live,
            queue_depth=queue_depth, tokens=tokens, preemptions=0,
            pages_in_use=0, page_occupancy=0.0,
            kv_bytes=dense, kv_bytes_dense=dense, prefix_hit_tokens=0,
            wall_s=wall, tokens_per_s=tokens / wall if wall > 0 else 0.0)
        self._emit_step(rec)
        self._wave_step += 1

    def _run_wave(self, wave: List[Request], queue_depth: int):
        b = self.batch_size
        plen = max(len(r.prompt) for r in wave)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(wave):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        cfg = self.model.cfg
        # Stubbed modality frontends (per assignment): frame/patch embeds.
        if cfg.family == "audio":
            batch["audio_embeds"] = jnp.zeros(
                (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (b, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
        t_pf = time.perf_counter()
        logits, caches = self._prefill(self.params, batch)
        jax.block_until_ready(logits)
        self._wave_record("prefill", len(wave), queue_depth, 0,
                          time.perf_counter() - t_pf)
        out = {r.uid: [] for r in wave}
        live = np.array([True] * len(wave) + [False] * (b - len(wave)))
        token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        max_new = max(r.max_new_tokens for r in wave)
        pos = plen
        for step in range(max_new):
            # Emission: live slots doing useful work this step over the
            # static batch width (the occupancy sample).
            t_it = time.perf_counter()
            n_live = int(live.sum())
            emitted = 0
            tok_np = np.asarray(token[:, 0])
            for i, r in enumerate(wave):
                if live[i]:
                    out[r.uid].append(int(tok_np[i]))
                    emitted += 1
                    if (int(tok_np[i]) == self.eos_id
                            or len(out[r.uid]) >= r.max_new_tokens):
                        live[i] = False
            if not live.any() or pos >= self.max_len - 1:
                # Final flush: tokens emitted, no decode ran.
                self._wave_record("emit", n_live, queue_depth, emitted,
                                  time.perf_counter() - t_it)
                break
            logits, caches = self._decode(self.params, token, caches,
                                          jnp.int32(pos))
            jax.block_until_ready(logits)
            token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            pos += 1
            self._wave_record("decode", n_live, queue_depth, emitted,
                              time.perf_counter() - t_it)
        return out
