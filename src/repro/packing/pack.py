"""Pack / unpack kernels: ahead-of-time tile packing with on-the-fly
transposition (the paper's §IV-C packing pass, run ONCE instead of per call).

``pack_operand`` reorders a weight into the plan's (bk, bn)-tiled block
layout described by :class:`repro.packing.layout.PackedLayout`:

* edge tiles are ZERO-padded (so the GEMM's K-tail needs no B-side
  predication and M/N-edge garbage cannot leak through the masked store),
* a ``trans_w`` source (stored (n, k)) is transposed DURING the pack —
  the paper's on-the-fly transposition, paid once,
* ``dtype="int8"`` quantizes each (bk, bn) tile symmetrically with its own
  f32 scale (per-tile, finer than ``core/quantization.py``'s per-tensor
  scheme) so the dequant rides the GEMM per tile.

Two implementations with identical semantics:

* a Pallas kernel (grid = tile grid, one tile per step) — the production
  path, used on the ``pallas``/``interpret`` backends;
* a pure-jnp reference (pad + reshape + transpose) — used on the ``xla``
  backend and under ``vmap`` (stacked-layer packing in ``params.py``).

``unpack_operand`` is the exact inverse (modulo quantization rounding) and
is what non-kernel backends and the backward pass use to recover a dense
operand.

Beyond int8, two sub-byte/low-precision codecs (``core.codecs``) share the
per-tile-scale machinery:

* ``int4`` — tiles quantize to +-7 and two K-adjacent values interleave
  into one payload byte (low nibble = even k, high nibble = odd k), so
  the payload moves HALF the bytes of int8.  ``unpack_nibbles`` is the
  in-register decode the GEMM kernel rides (sign-extending shifts).
* ``fp8e4m3`` — tiles scale by ``amax/448`` and saturating-cast to
  e4m3 (native ``jnp.float8_e4m3fn`` where available, emulated uint8
  bit codes otherwise — emulated payloads unpack on the XLA path only).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core import config as cfg
from repro.core.blocking import GemmPlan
from repro.core.codecs import (
    FP8_E4M3_MAX, HAS_JNP_FP8, canonical_payload_dtype, emulated_fp8_decode,
    emulated_fp8_encode, get_codec,
)
from repro.packing.layout import PackedLayout, PackedOperand


def _blocks_of(plan_or_blocks) -> Tuple[int, int]:
    if isinstance(plan_or_blocks, GemmPlan):
        return plan_or_blocks.bk, plan_or_blocks.bn
    bk, bn = plan_or_blocks
    return int(bk), int(bn)


def _layout_for(w, bk: int, bn: int, *, trans_w: bool, dtype,
                grouped: bool) -> PackedLayout:
    shape = w.shape[1:] if grouped else w.shape
    if len(shape) != 2:
        raise ValueError(f"pack_operand expects a 2-D (or grouped 3-D) "
                         f"operand, got {w.shape}")
    k, n = (shape[1], shape[0]) if trans_w else shape
    # Clamp blocks to the problem extent (mirrors plan_with_blocks): a tiny
    # operand packs as a single exact-fit tile instead of a mostly-pad one.
    return PackedLayout(
        k=k, n=n, bk=min(bk, k), bn=min(bn, n),
        dtype=canonical_payload_dtype(dtype if dtype is not None else w.dtype),
        orig_dtype=str(jnp.dtype(w.dtype)), trans_w=trans_w,
        g=w.shape[0] if grouped else 1,
    )


def _strip_group(layout: PackedLayout) -> PackedLayout:
    return dataclasses.replace(layout, g=1)


# --- pure-jnp reference (xla backend, vmap-able) ------------------------------

def _pack_dense_ref(w2d, layout: PackedLayout):
    """(k, n) / (n, k) source -> zero-padded (nkb, nnb, bk, bn) tiles."""
    if layout.trans_w:
        w2d = w2d.T
    k, n, bk, bn = layout.k, layout.n, layout.bk, layout.bn
    wp = jnp.pad(w2d, ((0, layout.nkb * bk - k), (0, layout.nnb * bn - n)))
    return wp.reshape(layout.nkb, bk, layout.nnb, bn).transpose(0, 2, 1, 3)


def pack_nibbles(q):
    """Interleave K-adjacent int4 values into bytes along the tile's K
    axis: (..., bk, bn) int8 values in [-7, 7] -> (..., ceil(bk/2), bn)
    int8 bytes, low nibble = even k, high nibble = odd k (odd bk zero-pads
    the dangling high nibble)."""
    bk = q.shape[-2]
    if bk % 2:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 2) + [(0, 1), (0, 0)])
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8)


def unpack_nibbles(b, rows: int):
    """Inverse of :func:`pack_nibbles` — the in-register decode the GEMM
    kernel uses: sign-extend each nibble with arithmetic shifts, then
    interleave back to ``rows`` logical K rows.  The shifts run on int32
    (Mosaic has no int8 vector shifts); arithmetic right shifts
    sign-extend."""
    b32 = b.astype(jnp.int32)
    lo = (b32 << 28) >> 28
    hi = b32 >> 4
    pair = jnp.stack((lo, hi), axis=-2)   # (..., hk, 2, bn)
    full = pair.reshape(*b.shape[:-2], 2 * b.shape[-2], b.shape[-1])
    return full[..., :rows, :].astype(jnp.int8)


def _encode_quant_tiles(tiles, codec):
    """Per-tile symmetric quantization for one codec: (..., bk, bn) ->
    (payload tiles in the codec's storage dtype, f32 scales).  int4
    payloads are nibble-packed (physical rows = ceil(bk/2))."""
    t32 = tiles.astype(jnp.float32)
    amax = jnp.max(jnp.abs(t32), axis=(-2, -1))
    scales = jnp.maximum(amax, 1e-8) / codec.qmax
    scaled = t32 / scales[..., None, None]
    if codec.integer:
        q = jnp.clip(jnp.round(scaled),
                     -codec.qmax, codec.qmax).astype(jnp.int8)
        if codec.elems_per_byte > 1:
            q = pack_nibbles(q)
        return q, scales.astype(jnp.float32)
    # fp8e4m3: saturating cast — e4m3fn has no inf, so clamp to the max
    # finite magnitude instead of overflowing to NaN.
    q = jnp.clip(scaled, -FP8_E4M3_MAX, FP8_E4M3_MAX)
    if HAS_JNP_FP8:
        return q.astype(jnp.float8_e4m3fn), scales.astype(jnp.float32)
    return emulated_fp8_encode(q), scales.astype(jnp.float32)


def _quantize_tiles_ref(tiles):
    """int8 per-tile quantization — the tile-sparse path's fixed codec
    (sparse payloads stay int8; :func:`_encode_quant_tiles` is the
    codec-general form the packed path uses)."""
    return _encode_quant_tiles(tiles, get_codec("int8"))


def decode_payload_tiles(payload, layout: PackedLayout):
    """Payload tiles -> per-element values (pre-scale): int4 nibbles
    sign-extend and interleave back to bk rows, emulated-fp8 bit codes
    decode to f32, byte-native payloads pass through."""
    codec = layout.codec
    if codec is None:
        return payload
    if codec.elems_per_byte > 1:
        return unpack_nibbles(payload, layout.bk)
    if not codec.integer and not codec.kernel_native:
        return emulated_fp8_decode(payload)
    return payload


def pack_reference(w, layout: PackedLayout):
    """The jnp pack: (payload, scales|None).  Also the payload-cotangent
    map used by the packed ops' VJP (linear for float payloads)."""
    if layout.g != 1:
        tiles = jax.vmap(
            lambda x: _pack_dense_ref(x, _strip_group(layout)))(w)
    else:
        tiles = _pack_dense_ref(w, layout)
    if layout.per_tile_scales:
        return _encode_quant_tiles(tiles, layout.codec)
    return tiles.astype(layout.storage_dtype), None


def _unpack_tiles_ref(tiles, layout: PackedLayout):
    full = tiles.transpose(0, 2, 1, 3).reshape(
        layout.nkb * layout.bk, layout.nnb * layout.bn)
    return full[: layout.k, : layout.n]


def unpack_reference(payload, scales, layout: PackedLayout, dtype):
    tiles = decode_payload_tiles(payload, layout)
    if scales is not None:
        tiles = tiles.astype(jnp.float32) * scales[..., None, None]
    if layout.g != 1:
        inner = _strip_group(layout)
        return jax.vmap(
            lambda t: _unpack_tiles_ref(t, inner))(tiles).astype(dtype)
    return _unpack_tiles_ref(tiles, layout).astype(dtype)


# --- Pallas kernels -----------------------------------------------------------

def _masked_tile(src_ref, i, j, layout: PackedLayout):
    """Read one source tile at tile-grid (i, j), transpose-resolved, with
    out-of-bounds lanes zeroed: edge tiles of a non-multiple operand read
    pipeline pad garbage (possibly NaN) which must never reach the payload
    — zero pads are what let the GEMM skip B-side K-edge predication."""
    tile = src_ref[...].reshape(src_ref.shape[-2:])
    if layout.trans_w:
        tile = tile.T                      # (bn, bk) storage -> (bk, bn)
    rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    valid_r = layout.k - i * layout.bk
    valid_c = layout.n - j * layout.bn
    return jnp.where((rows < valid_r) & (cols < valid_c), tile,
                     jnp.zeros_like(tile))


def _tile_ids(grouped: bool):
    return ((pl.program_id(1), pl.program_id(2)) if grouped
            else (pl.program_id(0), pl.program_id(1)))


def _pack_kernel(src_ref, out_ref, *, layout: PackedLayout, grouped: bool):
    tile = _masked_tile(src_ref, *_tile_ids(grouped), layout)
    out_ref[...] = tile.astype(out_ref.dtype).reshape(out_ref.shape)


def _pack_quant_kernel(src_ref, out_ref, scale_ref, *, layout: PackedLayout,
                       grouped: bool):
    codec = layout.codec
    tile = _masked_tile(src_ref, *_tile_ids(grouped), layout)
    tile = tile.astype(jnp.float32)
    amax = jnp.max(jnp.abs(tile))
    scale = jnp.maximum(amax, 1e-8) / codec.qmax
    scaled = tile / scale
    if codec.integer:
        q = jnp.clip(jnp.round(scaled),
                     -codec.qmax, codec.qmax).astype(jnp.int8)
        if codec.elems_per_byte > 1:
            q = pack_nibbles(q)
    else:
        # Saturating e4m3 cast (kernel path requires the native dtype).
        q = jnp.clip(scaled, -FP8_E4M3_MAX,
                     FP8_E4M3_MAX).astype(out_ref.dtype)
    out_ref[...] = q.astype(out_ref.dtype).reshape(out_ref.shape)
    scale_ref[...] = jnp.full(scale_ref.shape, scale, jnp.float32)


def _unpack_kernel(payload_ref, out_ref, *, dtype):
    out_ref[...] = payload_ref[...].reshape(out_ref.shape).astype(dtype)


def _unpack_quant_kernel(payload_ref, scale_ref, out_ref, *, dtype,
                         layout: PackedLayout):
    tile = payload_ref[...].reshape(payload_ref.shape[-2:])
    tile = decode_payload_tiles(tile, layout).astype(jnp.float32)
    tile = tile * scale_ref[0].reshape(-1)[0]
    out_ref[...] = tile.astype(dtype).reshape(out_ref.shape)


def _src_spec(layout: PackedLayout, grouped: bool):
    bk, bn = layout.bk, layout.bn
    if layout.trans_w:
        block, imap = (bn, bk), lambda i, j: (j, i)
    else:
        block, imap = (bk, bn), lambda i, j: (i, j)
    if grouped:
        return pl.BlockSpec((1,) + block,
                            lambda g, i, j: (g,) + imap(i, j))
    return pl.BlockSpec(block, imap)


def _payload_spec(layout: PackedLayout, grouped: bool):
    tile = layout.payload_tile
    if grouped:
        return pl.BlockSpec((1, 1, 1) + tile,
                            lambda g, i, j: (g, i, j, 0, 0))
    return pl.BlockSpec((1, 1) + tile, lambda i, j: (i, j, 0, 0))


def _scales_spec(grouped: bool):
    if grouped:
        return pl.BlockSpec((1, 1, 1), lambda g, i, j: (g, i, j))
    return pl.BlockSpec((1, 1), lambda i, j: (i, j))


def _pack_pallas(w, layout: PackedLayout, *, interpret: bool):
    grouped = layout.g != 1
    grid = ((layout.g,) if grouped else ()) + (layout.nkb, layout.nnb)
    src_spec = _src_spec(layout, grouped)
    payload_spec = _payload_spec(layout, grouped)
    if not layout.per_tile_scales:
        kernel = functools.partial(_pack_kernel, layout=layout,
                                   grouped=grouped)
        payload = pl.pallas_call(
            kernel, grid=grid, in_specs=[src_spec], out_specs=payload_spec,
            out_shape=jax.ShapeDtypeStruct(layout.payload_shape,
                                           layout.storage_dtype),
            interpret=interpret,
        )(w)
        return payload, None
    kernel = functools.partial(_pack_quant_kernel, layout=layout,
                               grouped=grouped)
    payload, scales = pl.pallas_call(
        kernel, grid=grid, in_specs=[src_spec],
        out_specs=[payload_spec, _scales_spec(grouped)],
        out_shape=[
            jax.ShapeDtypeStruct(layout.payload_shape, layout.storage_dtype),
            jax.ShapeDtypeStruct(layout.scales_shape, jnp.float32),
        ],
        interpret=interpret,
    )(w)
    return payload, scales


def _unpack_pallas(p: PackedOperand, dtype, *, interpret: bool):
    layout = p.layout
    grouped = layout.g != 1
    grid = ((layout.g,) if grouped else ()) + (layout.nkb, layout.nnb)
    out_spec = pl.BlockSpec(
        ((1,) if grouped else ()) + (layout.bk, layout.bn),
        (lambda g, i, j: (g, i, j)) if grouped else (lambda i, j: (i, j)))
    out_shape = jax.ShapeDtypeStruct(
        ((layout.g,) if grouped else ()) + (layout.k, layout.n),
        jnp.dtype(dtype))
    if p.scales is None:
        kernel = functools.partial(_unpack_kernel, dtype=jnp.dtype(dtype))
        return pl.pallas_call(
            kernel, grid=grid, in_specs=[_payload_spec(layout, grouped)],
            out_specs=out_spec, out_shape=out_shape, interpret=interpret,
        )(p.payload)
    kernel = functools.partial(_unpack_quant_kernel, dtype=jnp.dtype(dtype),
                               layout=layout)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[_payload_spec(layout, grouped), _scales_spec(grouped)],
        out_specs=out_spec, out_shape=out_shape, interpret=interpret,
    )(p.payload, p.scales)


# --- public API ---------------------------------------------------------------

def _resolve_method(backend: Optional[str]) -> str:
    backend = backend or cfg.get_gemm_backend()
    return backend if backend in ("pallas", "interpret", "xla") else "xla"


def pack_operand(
    w,
    plan_or_blocks: Union[GemmPlan, Tuple[int, int]],
    *,
    trans_w: bool = False,
    dtype=None,
    backend: Optional[str] = None,
) -> PackedOperand:
    """Pack a (k, n) / (n, k) weight — or a grouped (g, ., .) stack — into
    the (bk, bn)-tiled block layout of ``plan_or_blocks``.

    ``dtype`` selects the payload: a float dtype stores cast tiles; a
    codec name (``"int8"`` / ``"int4"`` / ``"fp8e4m3"``, aliases like
    ``"fp8"`` accepted) stores per-tile symmetrically-quantized tiles plus
    f32 scales — int4 nibble-packs two K-adjacent values per byte.
    Defaults to the source dtype.  The result is a :class:`PackedOperand`
    consumable by ``mp_dot(x, packed)`` / ``mpgemm_pallas(a, packed)``.
    """
    bk, bn = _blocks_of(plan_or_blocks)
    grouped = w.ndim == 3
    layout = _layout_for(w, bk, bn, trans_w=trans_w, dtype=dtype,
                         grouped=grouped)
    method = _resolve_method(backend)
    if not layout.kernel_native:
        method = "xla"          # emulated fp8 encodes via the jnp table
    with obs.span("pack", dtype=str(layout.dtype), bk=bk, bn=bn,
                  g=layout.g, method=method):
        if method == "xla":
            payload, scales = pack_reference(w, layout)
        else:
            payload, scales = _pack_pallas(w, layout,
                                           interpret=(method == "interpret"))
        obs.annotate(payload_bytes=int(payload.size)
                     * jnp.dtype(payload.dtype).itemsize)
    return PackedOperand(payload, scales, layout)


def unpack_operand(p: PackedOperand, *, dtype=None,
                   backend: Optional[str] = None):
    """Inverse of :func:`pack_operand`: dense (k, n) (grouped: (g, k, n)),
    transpose already resolved.  Quantized payloads (int8/int4/fp8e4m3)
    dequantize per tile; float payloads round-trip exactly.  ``dtype``
    defaults to the payload dtype (quantized codecs: the source dtype
    recorded at pack time)."""
    layout = p.layout
    if dtype is None:
        dtype = layout.orig_dtype if layout.per_tile_scales else layout.dtype
    method = _resolve_method(backend)
    if not layout.kernel_native:
        method = "xla"          # emulated fp8 decodes via the jnp table
    if method == "xla":
        return unpack_reference(p.payload, p.scales, layout, dtype)
    return _unpack_pallas(p, dtype, interpret=(method == "interpret"))
