"""Work the served tokens need, counted from the configuration alone.

Every roofline and MFU figure of the benchmark divides a least time
computed here by a time from the device trace.  The counts come from the
model's sizes (the configuration file) and the tokens and context
lengths a step processed; never from a kernel's grid or a planner, so a
kernel that skips dead work (padding rows, idle slots, unused pages)
scores higher, not lower.

A step is a list of rows, one per slot that did work: ``(q, s0)`` is a
slot that ran ``q`` query tokens at positions ``s0 .. s0 + q - 1``.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Tuple

BF16 = 2   # bytes: weights, activations and KV are stored in bfloat16

Rows = List[Tuple[int, int]]


@dataclasses.dataclass(frozen=True)
class Peaks:
    device_kind: str
    bf16_flops: float     # FLOP/s
    hbm_bytes_per_s: float


def load_peaks(device_kind: str, path: str = None) -> Peaks:
    """The published peaks of ``device_kind``; an unknown device raises."""
    path = path or os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {os.path.basename(path)} (known: {sorted(table)})")
    p = table[device_kind]
    return Peaks(device_kind, float(p["bf16_flops_per_s"]),
                 float(p["hbm_bytes_per_s"]))


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the counts need, read from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int       # 0: dense MLP
    top_k: int

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0))


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    least_s: float = 0.0   # sum over kernel calls of max(flops/peak, bytes/bw)

    def add(self, flops: float, nbytes: float, peaks: Peaks) -> None:
        self.flops += flops
        self.bytes += nbytes
        self.least_s += max(flops / peaks.bf16_flops,
                            nbytes / peaks.hbm_bytes_per_s)

    def __iadd__(self, other: "Work") -> "Work":
        self.flops += other.flops
        self.bytes += other.bytes
        self.least_s += other.least_s
        return self


def _matmul(work: Work, m: int, k: int, n: int, peaks: Peaks,
            weight_bytes: float = None) -> None:
    """One (m, k) x (k, n) product: weights read once, rows in and out."""
    if m == 0:
        return
    wb = k * n * BF16 if weight_bytes is None else weight_bytes
    work.add(2.0 * m * k * n, wb + (m * k + m * n) * BF16, peaks)


def gemm_work(s: Shape, rows: Rows, emitted: int, peaks: Peaks) -> Work:
    """The step's matrix products, as the GEMM kernels run them.

    Per layer: q, k, v and o projections; then the SwiGLU MLP (gate, up,
    down), or the router and the experts.  An expert projection reads at
    least the weights of ``top_k`` experts (every token picks that many
    distinct ones), which is the least any kernel can read.  The head
    projects only the rows that emit a token.
    """
    m = sum(q for q, _ in rows)
    w = Work()
    if m == 0:
        return w
    per = Work()
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    _matmul(per, m, s.d, qd, peaks)
    _matmul(per, m, s.d, kvd, peaks)
    _matmul(per, m, s.d, kvd, peaks)
    _matmul(per, m, qd, s.d, peaks)
    if s.experts:
        _matmul(per, m, s.d, s.experts, peaks)
        mk = m * s.top_k
        for k, n in ((s.d, s.ff), (s.d, s.ff), (s.ff, s.d)):
            _matmul(per, mk, k, n, peaks,
                    weight_bytes=min(s.top_k, s.experts) * k * n * BF16)
    else:
        for k, n in ((s.d, s.ff), (s.d, s.ff), (s.ff, s.d)):
            _matmul(per, m, k, n, peaks)
    for _ in range(s.layers):
        w += per
    _matmul(w, emitted, s.d, s.vocab, peaks)
    return w


def attention_work(s: Shape, rows: Rows, peaks: Peaks) -> Work:
    """Causal attention of the step's rows over their paged KV.

    A query at position p attends to the p + 1 keys at positions 0..p:
    QK^T and PV cost 4 * head_dim FLOPs per (query head, key).  The least
    bytes are each row's live K and V once per KV head, plus its queries
    and outputs.  One kernel call per layer.
    """
    flops = nbytes = 0.0
    for q, s0 in rows:
        keys = q * s0 + q * (q + 1) / 2          # sum of p + 1 over the rows
        flops += 4.0 * s.heads * s.head_dim * keys
        nbytes += (2 * s.kv_heads * (s0 + q) + 2 * s.heads * q) \
            * s.head_dim * BF16
    w = Work()
    if rows:
        for _ in range(s.layers):
            w.add(flops, nbytes, peaks)
    return w


def model_flops(s: Shape, rows: Rows, emitted: int, peaks: Peaks) -> float:
    """FLOPs the forward pass needs for the step's tokens (MFU numerator)."""
    return (gemm_work(s, rows, emitted, peaks).flops
            + attention_work(s, rows, peaks).flops)
