"""Attention cores: chunked (memory-efficient, differentiable), banded
sliding-window, single-step decode, and the Pallas flash kernel dispatch.

Backend policy mirrors core/gemm.py: on TPU the Pallas flash kernel runs; on
CPU (tests / dry-run) the pure-XLA chunked implementation lowers — identical
math, identical asymptotic memory behaviour (online softmax over KV blocks).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import config as cfg
from repro.distributed import act
from repro.kernels.flash_attention import flash_attention

NEG_INF = -1e30


def _repeat_kv(k, h: int):
    """(B, Hkv, T, D) -> (B, H, T, D) by group broadcast (no copy under XLA)."""
    b, hkv, t, d = k.shape
    if hkv == h:
        return k
    g = h // hkv
    return jnp.broadcast_to(k[:, :, None], (b, hkv, g, t, d)).reshape(b, h, t, d)


def _pad_heads_for_tp(q, k, v):
    """Pad the head dim to a multiple of the mesh's 'model' axis.

    When H does not divide the TP axis (phi3-medium: 40 heads on a 16-wide
    axis), the divisibility guard would REPLICATE attention across the axis
    — 16x the flops and logits traffic per device.  Padding to the next
    multiple (40->48) costs 20% padded compute but shards 16 ways.  K/V
    are expanded to full MHA first so padded q heads pair with zero K/V
    (softmax over zero logits -> zero output, sliced off).
    Returns (q, k, v, original_h)."""
    h = q.shape[1]
    mesh = act.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return q, k, v, h
    m = mesh.shape["model"]
    if h % m == 0:
        return q, k, v, h
    hp = -(-h // m) * m
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    pad = [(0, 0), (0, hp - h), (0, 0), (0, 0)]
    return jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), h


def dense_attention(q, k, v, *, causal=True, window=None, scale=None, lengths=None):
    """Reference/dense path; fine for short T (smoke tests, decode)."""
    q, k, v, h_orig = _pad_heads_for_tp(q, k, v)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    q = act.constrain(q, "batch", "model", None, None)
    k = act.constrain(k, "batch", "model", None, None)
    v = act.constrain(v, "batch", "model", None, None)
    scale = scale if scale is not None else 1.0 / d ** 0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    qi = jnp.arange(tq)[:, None] + (tk - tq)
    ki = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    mask = mask[None, None]
    if lengths is not None:  # per-example valid KV length (decode)
        mask = mask & (ki[None, None] < lengths[:, None, None, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out[:, :h_orig]


def chunked_attention(
    q, k, v, *, causal=True, scale=None,
    q_chunk: int = 1024, kv_chunk: int = 1024,
):
    """Online-softmax attention scanning q-chunks x kv-chunks (XLA path).

    Memory is O(q_chunk * kv_chunk) per step instead of O(Tq*Tk); the scan
    body is checkpointed so backward recomputes chunk logits (flash-style).
    """
    q, k, v, h_orig = _pad_heads_for_tp(q, k, v)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    q = act.constrain(q, "batch", "model", None, None)
    k = act.constrain(k, "batch", "model", None, None)
    v = act.constrain(v, "batch", "model", None, None)
    scale = scale if scale is not None else 1.0 / d ** 0.5
    q_chunk = min(q_chunk, tq)
    kv_chunk = min(kv_chunk, tk)
    # Pad to chunk multiples.
    pq = (-tq) % q_chunk
    pk = (-tk) % kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = qp.shape[2] // q_chunk
    nk = kp.shape[2] // kv_chunk
    offset = tk - tq  # right-aligned causal (prefill continuation)

    def _block(i, j, qblk, kblk, vblk, m, l, acc, need_mask=True):
        """One (q-chunk i, kv-chunk j) online-softmax update.

        ``need_mask=False`` skips the causal/tail select pass entirely —
        valid for strictly-below-diagonal blocks when q_chunk == kv_chunk
        and tq == tk (every key predates every query and no tail padding
        is touched).  Elides a full read+write over the logits block."""
        s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kblk,
                       preferred_element_type=jnp.float32) * scale
        if need_mask:
            qpos = i * q_chunk + jnp.arange(q_chunk)[:, None] + offset
            kpos = j * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = kpos < tk
            if causal:
                mask &= kpos <= qpos
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def _init(nq_):
        return (
            act.constrain(jnp.full((b, h, nq_ * q_chunk), NEG_INF,
                                   jnp.float32), "batch", "model", None),
            act.constrain(jnp.zeros((b, h, nq_ * q_chunk), jnp.float32),
                          "batch", "model", None),
            act.constrain(jnp.zeros((b, h, nq_ * q_chunk, d), jnp.float32),
                          "batch", "model", None, None),
        )

    if causal and nq > 1 and tq == tk:
        # TRIANGULAR block schedule: only (i, j<=i) pairs are visited, so
        # the ~half of blocks that the causal mask fully kills never load,
        # compute, or spill logits.  Strictly-below-diagonal pairs
        # additionally skip the mask select pass when chunk sizes allow.
        maskless_ok = (q_chunk == kv_chunk)

        def make_step(need_mask):
            def pair_step(carry, ij):
                m, l, acc = carry
                i, j = ij
                qblk = jax.lax.dynamic_slice(
                    qp, (0, 0, i * q_chunk, 0), (b, h, q_chunk, d))
                kblk = jax.lax.dynamic_slice(
                    kp, (0, 0, j * kv_chunk, 0), (b, h, kv_chunk, d))
                vblk = jax.lax.dynamic_slice(
                    vp, (0, 0, j * kv_chunk, 0), (b, h, kv_chunk, d))
                mi = jax.lax.dynamic_slice(
                    m, (0, 0, i * q_chunk), (b, h, q_chunk))
                li = jax.lax.dynamic_slice(
                    l, (0, 0, i * q_chunk), (b, h, q_chunk))
                ai = jax.lax.dynamic_slice(
                    acc, (0, 0, i * q_chunk, 0), (b, h, q_chunk, d))
                mi, li, ai = _block(i, j, qblk, kblk, vblk, mi, li, ai,
                                    need_mask=need_mask)
                m = jax.lax.dynamic_update_slice(m, mi, (0, 0, i * q_chunk))
                l = jax.lax.dynamic_update_slice(l, li, (0, 0, i * q_chunk))
                acc = jax.lax.dynamic_update_slice(
                    acc, ai, (0, 0, i * q_chunk, 0))
                return (m, l, acc), None
            return pair_step

        carry = _init(nq)
        offdiag = [(i, j) for i in range(nq) for j in range(i)]
        if offdiag and maskless_ok:
            pi = jnp.asarray([p_[0] for p_ in offdiag], jnp.int32)
            pj = jnp.asarray([p_[1] for p_ in offdiag], jnp.int32)
            carry, _ = jax.lax.scan(
                jax.checkpoint(make_step(False)), carry, (pi, pj))
            diag = [(i, i) for i in range(nq)]
        else:
            diag = [(i, j) for i in range(nq) for j in range(i + 1)]
        pi = jnp.asarray([p_[0] for p_ in diag], jnp.int32)
        pj = jnp.asarray([p_[1] for p_ in diag], jnp.int32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(make_step(True)), carry, (pi, pj))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return out[:, :h_orig, :tq]

    # Rectangular schedule (cross-attention / uneven tq,tk).
    qs = qp.reshape(b, h, nq, q_chunk, d).transpose(2, 0, 1, 3, 4)
    ks = kp.reshape(b, h, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vs = vp.reshape(b, h, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)

    def q_step(_, qi_blk):
        qi_idx, qblk = qi_blk

        def kv_step(carry, kv_blk):
            m, l, acc = carry
            kj_idx, kblk, vblk = kv_blk
            m, l, acc = _block(qi_idx, kj_idx, qblk, kblk, vblk, m, l, acc)
            return (m, l, acc), None

        init = (
            act.constrain(jnp.full((b, h, q_chunk), NEG_INF, jnp.float32),
                          "batch", "model", None),
            act.constrain(jnp.zeros((b, h, q_chunk), jnp.float32),
                          "batch", "model", None),
            act.constrain(jnp.zeros((b, h, q_chunk, d), jnp.float32),
                          "batch", "model", None, None),
        )
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), init, (jnp.arange(nk), ks, vs)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, nq * q_chunk, d)
    return out[:, :h_orig, :tq]


def banded_window_attention(q, k, v, *, window: int, scale=None):
    """Sliding-window self-attention with truly sub-quadratic FLOPs.

    Queries are grouped into blocks of size ``window``; each block attends to
    itself and its predecessor (2*window keys) under the exact causal+window
    mask.  HLO FLOPs are O(T * 2*window * d) — this is what makes the
    long_500k shape lowerable for SWA architectures.
    """
    q, k, v, h_orig = _pad_heads_for_tp(q, k, v)
    b, h, t, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    q = act.constrain(q, "batch", "model", None, None)
    k = act.constrain(k, "batch", "model", None, None)
    v = act.constrain(v, "batch", "model", None, None)
    scale = scale if scale is not None else 1.0 / d ** 0.5
    w = window
    pad = (-t) % w
    tp = t + pad
    nb = tp // w
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(b, h, nb, w, d)
    kb = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(b, h, nb, w, d)
    vb = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(b, h, nb, w, d)
    # Previous block of K/V (zeros for block 0).
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :, :1]), kb[:, :, :-1]], axis=2)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :, :1]), vb[:, :, :-1]], axis=2)
    k2 = jnp.concatenate([kprev, kb], axis=3)   # (b,h,nb,2w,d)
    v2 = jnp.concatenate([vprev, vb], axis=3)
    s = jnp.einsum("bhnqd,bhnkd->bhnqk", qb, k2,
                   preferred_element_type=jnp.float32) * scale
    qi = jnp.arange(w)[:, None] + w             # position within [prev|self]
    ki = jnp.arange(2 * w)[None, :]
    mask = (ki <= qi) & (ki > qi - w)
    blk0_mask = mask & (ki >= w)                # block 0 has no predecessor
    bidx = jnp.arange(nb)[:, None, None]
    full_mask = jnp.where(bidx == 0, blk0_mask[None], mask[None])
    s = jnp.where(full_mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhnqk,bhnkd->bhnqd", p.astype(v2.dtype), v2,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, h, tp, d)[:, :h_orig, :t]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, window=None, scale=None):
    """One-token attention against a (possibly ring-buffered) KV cache.

    q: (B, H, 1, D); caches: (B, Hkv, S, D); lengths: (B,) valid entries.
    GQA is computed in GROUPED form — q reshaped to (B, Hkv, G, D) and
    contracted against the (B, Hkv, S, D) cache directly — so the KV heads
    are never repeated/materialized.  This keeps the cache's
    sequence-parallel sharding (S over 'model') intact: the softmax
    reductions over the sharded S axis lower to small all-reduces
    (flash-decode style) instead of cache replication.

    For ring caches (SWA), entries are stored mod S and all S slots are
    valid once the ring has wrapped — the mask is on slot validity, not
    recency (the ring overwrite already evicts out-of-window keys).
    """
    b, h, _, d = q.shape
    hkv, s_max = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = scale if scale is not None else 1.0 / d ** 0.5
    # Barrier anchors the (layer-sliced) cache values inside the layer loop:
    # without it, XLA:CPU hoists the bf16->f32 dot-operand upcast out of the
    # loop and maintains a full f32 shadow copy of the stacked cache in the
    # while carry (2x cache memory + full-cache converts every iteration).
    k_cache, v_cache = jax.lax.optimization_barrier((k_cache, v_cache))
    logits = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s_max)[None, None, None] < lengths[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)
    return o.reshape(b, h, 1, d).astype(q.dtype)


from jax.sharding import PartitionSpec as P


def flash_decode_sharded(q, k_cache, v_cache, k_new, v_new, pos, mesh,
                         *, scale=None):
    """Sequence-parallel flash decode under shard_map.

    The KV cache stays sharded (batch over the data axes, sequence over
    'model').  Each model shard:
      * writes the new K/V row ONLY if the ring slot falls in its range
        (lax.cond — no full-cache select rewrite, unlike partitioned DUS),
      * computes attention over its local sequence chunk in f32 (cast of a
        bounded per-layer slice — no whole-stack f32 shadow copies),
      * combines with a log-sum-exp psum over 'model' (flash-decode).
    This is the distributed analogue of the paper's K-dim blocking with a
    resident accumulator: the reduction is streamed in shards and combined
    once.  Returns (o, k_cache, v_cache)."""
    b, h, _, d = q.shape
    hkv = k_cache.shape[1]
    sm = scale if scale is not None else 1.0 / d ** 0.5
    m_size = mesh.shape["model"]

    def body(q, kc, vc, kn, vn, pos):
        sl = kc.shape[2]
        midx = jax.lax.axis_index("model")
        slot = pos % (sl * m_size)
        local_start = midx * sl
        in_range = (slot >= local_start) & (slot < local_start + sl)

        def write(c, new):
            return jax.lax.dynamic_update_slice(
                c, new.astype(c.dtype), (0, 0, slot - local_start, 0))

        kc = jax.lax.cond(in_range, lambda: write(kc, kn), lambda: kc)
        vc = jax.lax.cond(in_range, lambda: write(vc, vn), lambda: vc)

        bl = q.shape[0]
        g = h // hkv
        # Keep cache operands in their stored bf16 and accumulate f32 via
        # preferred_element_type: casting the cache slice to f32 here makes
        # XLA maintain a full f32 shadow of the stacked cache in the layer
        # scan carry.
        qg = q.reshape(bl, hkv, g, d).astype(kc.dtype)
        logits = jnp.einsum("bhgd,bhkd->bhgk", qg, kc,
                            preferred_element_type=jnp.float32) * sm
        length = jnp.minimum(pos + 1, sl * m_size)
        valid = (local_start + jnp.arange(sl))[None, None, None] < length
        logits = jnp.where(valid, logits, NEG_INF)
        m_loc = logits.max(-1)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(logits - m_glob[..., None])
        l_glob = jax.lax.psum(p.sum(-1), "model")
        o_glob = jax.lax.psum(
            jnp.einsum("bhgk,bhkd->bhgd", p.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32), "model")
        o = o_glob / jnp.maximum(l_glob[..., None], 1e-30)
        return o.reshape(bl, h, 1, d).astype(q.dtype), kc, vc

    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = da if len(da) > 1 else da[0]
    qs = P(bspec, None, None, None)
    cs = P(bspec, None, "model", None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qs, cs, cs, qs, qs, P()),
        out_specs=(qs, cs, cs),
    )(q, k_cache, v_cache, k_new, v_new, pos)


def can_flash_decode(q, k_cache, mesh) -> bool:
    import os
    if os.environ.get("REPRO_NO_FLASH_DECODE"):
        return False
    if mesh is None or "model" not in mesh.axis_names:
        return False
    b, h = q.shape[0], q.shape[1]
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    ddp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            ddp *= mesh.shape[a]
    return (b % ddp == 0 and s % mesh.shape["model"] == 0
            and h % hkv == 0 and "data" in mesh.axis_names)


def attention_core(
    q, k, v, *, causal=True, window: Optional[int] = None, scale=None,
    backend: Optional[str] = None,
):
    """Prefill/train dispatch: Pallas flash on TPU, chunked/banded on XLA."""
    backend = backend or cfg.get_gemm_backend()
    t = q.shape[2]
    if backend in ("pallas", "interpret"):
        return flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=(backend == "interpret"),
        )
    if window is not None and causal and t > 2 * window:
        return banded_window_attention(q, k, v, window=window, scale=scale)
    if t <= 2048:
        return dense_attention(q, k, v, causal=causal, window=window, scale=scale)
    return chunked_attention(q, k, v, causal=causal, scale=scale)


# --------------------------------------------------------------------------
# Paged attention (continuous-batching serving; docs/serving.md)
#
# KV lives in a pooled page array (kernels.flash_attention.kv_page_shape:
# (P, Hkv/hb, page_size, lanes), hb heads per row); each request's
# logical KV stream is the concatenation of the pages its block-table row
# names.  Positions in the math below are LOGICAL (page j, offset o ->
# j*page_size + o); which physical page backs them is irrelevant to masking.
# --------------------------------------------------------------------------


def paged_kv_write(k_pages, v_pages, k_new, v_new, block_tables, q_start,
                   n_valid):
    """Scatter a (B, C) chunk of fresh K/V rows into the page pool.

    ``k_new``/``v_new``: (B, Hkv, C, D); token i of request b lands at
    logical position ``q_start[b] + i`` -> physical page
    ``block_tables[b, pos // ps]``, offset ``pos % ps``, its heads side by
    side as the pool's rows hold them (``kv_page_shape``).  Rows with
    ``i >= n_valid[b]`` are dead: they are routed to the reserved scratch
    page 0 (slot ``(b*C + i) % ps`` — scratch content is never read as
    valid, the attention mask kills it).
    """
    b, hkv, c, d = k_new.shape
    groups, ps = k_pages.shape[1:3]
    w = block_tables.shape[1]
    pos = q_start[:, None] + jnp.arange(c)[None, :]            # (B, C)
    page = jnp.take_along_axis(
        block_tables, jnp.clip(pos // ps, 0, w - 1), axis=1)   # (B, C)
    offset = pos % ps
    valid = jnp.arange(c)[None, :] < n_valid[:, None]
    scratch_off = (jnp.arange(c)[None, :] + jnp.arange(b)[:, None] * c) % ps
    page = jnp.where(valid, page, 0)
    offset = jnp.where(valid, offset, scratch_off)
    pg = page.reshape(-1)
    off = offset.reshape(-1)
    width = hkv // groups * d                      # a row's heads, unpadded
    k_rows = k_new.transpose(0, 2, 1, 3).reshape(b * c, groups, width)
    v_rows = v_new.transpose(0, 2, 1, 3).reshape(b * c, groups, width)
    k_pages = k_pages.at[pg, :, off, :width].set(k_rows.astype(k_pages.dtype))
    v_pages = v_pages.at[pg, :, off, :width].set(v_rows.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_attention_ref(q, k_pages, v_pages, block_tables, q_start, lengths,
                        *, causal=True, window=None, scale=None):
    """Pure-XLA paged attention: gather the block-table pages into a dense
    per-request KV stream, then masked grouped-GQA softmax.  Numerically
    the oracle for the Pallas kernel and the CPU serving path."""
    b, h, tq, d = q.shape
    p_pages, groups, ps, lanes = k_pages.shape
    width = lanes // d * d                         # a row's heads, unpadded
    hkv = groups * width // d
    w = block_tables.shape[1]
    g = h // hkv
    s_max = w * ps
    scale = scale if scale is not None else 1.0 / d ** 0.5
    tok = (block_tables[:, :, None] * ps
           + jnp.arange(ps)[None, None, :]).reshape(b, s_max)   # (B, S)
    kf = k_pages[..., :width].transpose(0, 2, 1, 3).reshape(
        p_pages * ps, hkv, d)
    vf = v_pages[..., :width].transpose(0, 2, 1, 3).reshape(
        p_pages * ps, hkv, d)
    k = kf[tok].transpose(0, 2, 1, 3)                           # (B, Hkv, S, D)
    v = vf[tok].transpose(0, 2, 1, 3)
    qg = q.reshape(b, hkv, g, tq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    qi = q_start[:, None] + jnp.arange(tq)[None, :]             # (B, Tq)
    ki = jnp.arange(s_max)
    mask = ki[None, None, :] < lengths[:, None, None]           # (B, 1, S)
    if causal:
        mask = mask & (ki[None, None, :] <= qi[:, :, None])     # (B, Tq, S)
    if window is not None:
        mask = mask & (ki[None, None, :] > qi[:, :, None] - window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)
    return o.reshape(b, h, tq, d).astype(q.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, q_start, lengths, *,
                    causal=True, window: Optional[int] = None, scale=None,
                    backend: Optional[str] = None):
    """Serving dispatch for paged KV: Pallas block-table kernel on TPU,
    XLA gather reference elsewhere (same math, same logical masking)."""
    backend = backend or cfg.get_gemm_backend()
    if backend in ("pallas", "interpret"):
        from repro.kernels.flash_attention import paged_flash_attention
        return paged_flash_attention(
            q, k_pages, v_pages, block_tables, q_start, lengths,
            causal=causal, window=window, scale=scale,
            interpret=(backend == "interpret"))
    return paged_attention_ref(
        q, k_pages, v_pages, block_tables, q_start, lengths,
        causal=causal, window=window, scale=scale)
