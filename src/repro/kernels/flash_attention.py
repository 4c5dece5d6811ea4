"""Blocked online-softmax attention (flash attention) as a Pallas TPU kernel.

This is the attention hot-spot counterpart of the MPGEMM kernel and follows
the same design discipline derived from the paper:

* resident accumulators in VMEM scratch across the KV (reduction) loop —
  the "all ZA tiles" rule applied to (acc, m, l);
* KV streamed in wide blocks (lane dim = head_dim, >=512B rows);
* predication (iota masks) for causal / sliding-window / KV-tail edges,
  the paper's predicate-register edge handling;
* GQA handled by a 5-D grid (b, kv_head, group, q_block, kv_block) so KV
  blocks are fetched once per group without materializing repeats.
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


def _paged_flash_kernel(
    bt_ref, qs_ref, len_ref,            # scalar-prefetch: block table, q_start, lengths
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, npages: int, bq: int, ps: int,
    causal: bool, window: Optional[int], scale: float,
):
    b_ = pl.program_id(0)
    qb = pl.program_id(3)
    j = pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)     # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)        # (ps, d)
    v = v_ref[0, 0].astype(jnp.float32)        # (ps, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # (bq, ps)

    # Positions are LOGICAL: page j holds kv tokens [j*ps, (j+1)*ps) of this
    # request's stream regardless of which physical page bt[b, j] names.
    length = len_ref[b_]
    qi = qs_ref[b_] + qb * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, ps), 0)
    ki = j * ps + jax.lax.broadcasted_iota(jnp.int32, (bq, ps), 1)
    mask = ki < length                          # ragged-length predication
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    s = jnp.where(mask, s, NEG_INF)
    # Zero V rows past the valid length: scratch-page garbage (and pipeline
    # pad NaNs) must never reach acc, even weighted by p == 0.
    vrow = j * ps + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(vrow < length, v, 0.0)

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

    @pl.when(j == npages - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0, 0] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)


def paged_flash_attention(
    q: jax.Array,             # (B, H, Tq, D) — current-chunk queries
    k_pages: jax.Array,       # (P, Hkv, page_size, D) — pooled KV pages
    v_pages: jax.Array,       # (P, Hkv, page_size, D)
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
    """Flash attention whose KV blocks are gathered through a block table.

    The grid is ``(B, Hkv, G, nq, W)`` with the page axis innermost; the
    K/V index maps read the scalar-prefetched block table — the same
    stored-schedule gather ``mpgemm``'s sparse launch uses for its tile
    schedule, so every grid step DMAs exactly the page the table names.
    Dead table slots point at the reserved scratch page (id 0): the DMA
    stays in-bounds and the logical-position mask (``ki < lengths[b]``)
    zeroes their contribution.
    """
    b, h, tq, d = q.shape
    p_pages, hkv, ps, dk = k_pages.shape
    if d != dk:
        raise ValueError(f"head_dim mismatch: q has {d}, pages have {dk}")
    if h % hkv:
        raise ValueError(f"GQA requires H % Hkv == 0, got {h} % {hkv}")
    if k_pages.shape != v_pages.shape:
        raise ValueError("k_pages / v_pages shape mismatch")
    g = h // hkv
    w = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq = min(block_q, max(8, tq))
    nq = pl.cdiv(tq, bq)

    q5 = q.reshape(b, hkv, g, tq, d)
    grid = (b, hkv, g, nq, w)
    kernel = functools.partial(
        _paged_flash_kernel, npages=w, bq=bq, ps=ps,
        causal=causal, window=window, scale=scale,
    )

    # Index maps see the grid indices plus the scalar-prefetch refs; the
    # flattened block table is indexed exactly like the sparse launch's
    # slot[] schedule.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, 1, bq, d),
                lambda b_, h_, g_, i, j, bt, qs, ln: (b_, h_, g_, i, 0)),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda b_, h_, g_, i, j, bt, qs, ln: (bt[b_ * w + j], h_, 0, 0)),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda b_, h_, g_, i, j, bt, qs, ln: (bt[b_ * w + j], h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, bq, d),
            lambda b_, h_, g_, i, j, bt, qs, ln: (b_, h_, g_, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out5 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, tq, d), q.dtype),
        name="paged_flash_attention",
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(
        block_tables.reshape(-1).astype(jnp.int32),
        q_start.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q5, k_pages, v_pages,
    )
    return out5.reshape(b, h, tq, d)
