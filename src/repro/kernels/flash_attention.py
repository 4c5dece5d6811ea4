"""Blocked online-softmax attention (flash attention) as Pallas TPU kernels.

Both kernels follow the design discipline the MPGEMM kernel derives from
the paper:

* resident accumulators in VMEM scratch across the KV (reduction) walk —
  the "all ZA tiles" rule applied to (acc, m, l);
* predication (iota masks) for causal / sliding-window / KV-tail edges,
  the paper's predicate-register edge handling.

``flash_attention`` (dense K/V, training and prefill) streams KV blocks
through the grid ``(b, kv_head, group, q_block, kv_block)``; each query
head of a GQA group walks its KV head's blocks again.

``paged_flash_attention`` (serving) reads K/V out of a page pool through
a block table.  Its grid is ``(b, head_group, q_block)``: the query heads
that share a page row are folded into the query rows, and the KV walk is
a loop inside each program that DMAs only the pages the row's length
makes live, each once per KV head (``kv_page_shape`` gives the pool's
layout).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Grid (b, kv_head, group, q_block, kv_block): the KV walk is the revisited
# reduction axis, every other axis is independent.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel",) * 4 + ("arbitrary",))


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, nk: int, bq: int, bk: int, tq: int, tk: int,
    causal: bool, window: Optional[int], scale: float, kv_rem: int,
):
    kb = pl.program_id(4)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)     # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # (bq, bk)

    qb = pl.program_id(3)
    qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (tk - tq)
    ki = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = ki < tk                              # KV tail predication
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    s = jnp.where(mask, s, NEG_INF)
    if kv_rem:
        # Zero padded V rows so 0 * NaN(pipeline pad) never reaches acc.
        vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + kb * bk
        v = jnp.where(vrow < tk, v, 0.0)

    m_prev = m_ref[:, :1]                       # (bq, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                      # (bq, bk)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True), l_ref.shape
    )
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )

    @pl.when(kb == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,       # (B, H, Tq, D)
    k: jax.Array,       # (B, Hkv, Tk, D)
    v: jax.Array,       # (B, Hkv, Tk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    if h % hkv:
        raise ValueError(f"GQA requires H % Hkv == 0, got {h} % {hkv}")
    g = h // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq = min(block_q, max(8, tq))
    bk = min(block_k, max(128, tk))
    nq = pl.cdiv(tq, bq)
    nk = pl.cdiv(tk, bk)

    q5 = q.reshape(b, hkv, g, tq, d)
    grid = (b, hkv, g, nq, nk)

    kernel = functools.partial(
        _flash_kernel, nk=nk, bq=bq, bk=bk, tq=tq, tk=tk,
        causal=causal, window=window, scale=scale, kv_rem=tk % bk,
    )
    out5 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, d), lambda b_, h_, g_, i, j: (b_, h_, g_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, g_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, g_, i, j: (b_, h_, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, bq, d), lambda b_, h_, g_, i, j: (b_, h_, g_, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q5, k, v)
    return out5.reshape(b, h, tq, d)


def kv_page_shape(num_pages: int, n_kv_heads: int, page_size: int,
                  head_dim: int) -> tuple:
    """Shape of one layer's K (or V) page pool: ``(num_pages, n_kv_heads //
    hb, page_size, lanes)``, ``hb = lanes // head_dim`` heads side by side
    in each page row.

    The TPU DMAs a (page_size, lanes) slab out of the pool only when its
    rows are whole 128-lane tiles: a narrower minor dim is padded in HBM
    and cannot be sliced.  So a row holds the fewest heads that fill whole
    tiles (2 of width 64, 4 of width 96, 1 of 128); where no group of
    heads does, one head padded to whole tiles (width 120 -> 128 lanes);
    below 64 lanes a head, all heads unpadded (interpret mode only).
    """
    hb = next((h for h in range(1, n_kv_heads + 1)
               if n_kv_heads % h == 0 and h * head_dim % 128 == 0), None)
    if hb is not None:
        return (num_pages, n_kv_heads // hb, page_size, hb * head_dim)
    lanes = pl.cdiv(head_dim, 128) * 128
    if lanes < 2 * head_dim:
        return (num_pages, n_kv_heads, page_size, lanes)
    return (num_pages, 1, page_size, n_kv_heads * head_dim)


# Grid (b, head_group, q_block): the KV walk is a loop inside each program.
_PAGED_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel",) * 3)


def _paged_flash_kernel(
    bt_ref, qs_ref, len_ref,            # scalar-prefetch: block table, q_start, lengths
    q_ref, k_hbm, v_hbm,                # q block in VMEM; page pools in HBM
    o_ref,
    k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
    *, w: int, ppb: int, ps: int, bq: int, tq: int,
    causal: bool, window: Optional[int], scale: float,
):
    b_ = pl.program_id(0)
    hg = pl.program_id(1)
    qb = pl.program_id(2)
    bk = ppb * ps
    lanes = acc_ref.shape[-1]
    length = len_ref[b_]
    n_live = (length + ps - 1) // ps             # pages this row has written
    n_blocks = (n_live + ppb - 1) // ppb

    def copies(blk, slot):
        """(live, K copy, V copy) for each page of block ``blk``; a table
        slot past the row's last live page is neither copied nor waited
        for, so no dead slot's page is ever fetched."""
        for i in range(ppb):
            j = blk * ppb + i
            page = bt_ref[b_ * w + jnp.minimum(j, w - 1)]
            yield (j < n_live,
                   pltpu.make_async_copy(k_hbm.at[page, hg], k_buf.at[slot, i],
                                         sems.at[0, slot]),
                   pltpu.make_async_copy(v_hbm.at[page, hg], v_buf.at[slot, i],
                                         sems.at[1, slot]))

    def start(blk, slot):
        for live, ck, cv in copies(blk, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(blk, slot):
        for live, ck, cv in copies(blk, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _prefetch_first():
        start(0, 0)

    q = q_ref[0, 0].astype(jnp.float32)         # (bq, lanes)
    # Row r of the program's query block is position q_start + r % Tq.
    row = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    qi = qs_ref[b_] + row % tq
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    vrow = jax.lax.broadcasted_iota(jnp.int32, (bk, lanes), 0)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch_next():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        k = k_buf[slot].astype(jnp.float32).reshape(bk, lanes)
        v = v_buf[slot].astype(jnp.float32).reshape(bk, lanes)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                               # (bq, bk)
        # Positions are LOGICAL: block blk holds kv tokens [blk*bk, (blk+1)*bk)
        # of this request's stream, whichever physical pages the table names.
        ki = blk * bk + col
        mask = ki < length                      # ragged-length predication
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= ki > qi - window
        s = jnp.where(mask, s, NEG_INF)
        # Zero V rows past the valid length: buffer slots no live page
        # filled hold stale data, which must never reach acc, even times 0.
        v = jnp.where(blk * bk + vrow < length, v, 0.0)

        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True), l_ref.shape
        )
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # A row with no live page walks nothing and writes zeros.
    o_ref[0, 0] = (
        acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
    ).astype(o_ref.dtype)


def paged_flash_attention(
    q: jax.Array,             # (B, H, Tq, D) — current-chunk queries
    k_pages: jax.Array,       # kv_page_shape(P, Hkv, page_size, D) pool
    v_pages: jax.Array,       # same shape as k_pages
    block_tables: jax.Array,  # (B, W) int32 physical page ids, 0-padded
    q_start: jax.Array,       # (B,) int32 absolute position of q row 0
    lengths: jax.Array,       # (B,) int32 total valid KV tokens
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention that walks each row's live KV pages once per KV head.

    Pages are ``(P, Hkv / hb, page_size, lanes)``: ``hb`` KV heads side
    by side in each page row (``kv_page_shape``).  The grid is ``(B,
    Hkv / hb, nq)``.  A program's query rows are every query head of its
    ``hb`` KV heads, the GQA group folded in (``hb * G * Tq`` rows, padded
    to a multiple of 8 sublanes and cut into ``nq`` blocks); each row
    carries its head's query in that head's lanes and zeros elsewhere, so
    one (rows, hb*D) x (hb*D, keys) product scores every head against its
    own keys, and each row's output is read back from its head's lanes.
    One page fetch thus serves all ``hb * G`` query heads.

    The KV walk is a loop inside each program, bounded by the row's
    length: ``cdiv(cdiv(lengths[b], page_size), ppb)`` blocks of ``ppb =
    max(1, 128 // page_size)`` pages, each page DMA'd from HBM into VMEM
    at the physical id the scalar-prefetched block table names, the next
    block's copies in flight while the current one computes.  No page past
    ``lengths[b]`` is fetched, and a row with ``lengths[b] == 0`` copies
    nothing and writes zeros.  Masks act on logical positions
    (``ki < lengths[b]``, causal, window).
    """
    b, h, tq, d = q.shape
    _, n_groups, ps, lanes = k_pages.shape
    if lanes < d:
        raise ValueError(f"page rows of {lanes} lanes cannot hold a head "
                         f"of width {d}")
    if k_pages.shape != v_pages.shape:
        raise ValueError("k_pages / v_pages shape mismatch")
    hb = lanes // d                   # heads side by side in a page row
    if h % (n_groups * hb):
        raise ValueError(f"GQA requires H % Hkv == 0, got {h} % "
                         f"{n_groups * hb}")
    g = h // (n_groups * hb)
    w = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    ppb = max(1, 128 // ps)
    rows = hb * g * tq
    bq = min(block_q, pl.cdiv(rows, 8) * 8)
    nq = pl.cdiv(rows, bq)

    # Query head (group, j, gi) goes to row (j*G + gi)*Tq + t, lanes of head j.
    q5 = q.reshape(b, n_groups, hb, g * tq, d)
    if hb > 1:
        own = jnp.eye(hb, dtype=bool)[:, None, :, None]
        q5 = jnp.where(own, q5[:, :, :, :, None, :], 0)
    q4 = q5.reshape(b, n_groups, rows, hb * d)
    if nq * bq > rows or lanes > hb * d:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, nq * bq - rows),
                          (0, lanes - hb * d)))
    kernel = functools.partial(
        _paged_flash_kernel, w=w, ppb=ppb, ps=ps, bq=bq, tq=tq,
        causal=causal, window=window, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_groups, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, lanes),
                         lambda b_, g_, i, bt, qs, ln: (b_, g_, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, lanes),
                               lambda b_, g_, i, bt, qs, ln: (b_, g_, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, lanes), k_pages.dtype),
            pltpu.VMEM((2, ppb, ps, lanes), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_groups, nq * bq, lanes), q.dtype),
        name="paged_flash_attention",
        compiler_params=_PAGED_COMPILER_PARAMS,
        interpret=interpret,
    )(
        block_tables.reshape(-1).astype(jnp.int32),
        q_start.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q4, k_pages, v_pages,
    )
    out = out[:, :, :rows, :hb * d].reshape(b, n_groups, hb, g * tq, hb, d)
    # Each row's output lies in its own head's lanes.
    out = jnp.stack([out[:, :, j, :, j] for j in range(hb)], axis=2)
    return out.reshape(b, h, tq, d)
