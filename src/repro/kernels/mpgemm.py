"""MPGEMM-TPU: ONE spec-driven Pallas kernel factory.

TPU-native re-derivation of the paper's SME micro-kernel (Sections IV-C,
V-C), generated from a :class:`~repro.core.gemm_spec.GemmSpec` +
:class:`~repro.core.gemm_spec.EpilogueSpec` instead of hand-cloned per
path:

* "All four ZA tiles resident across the K loop"  ->  an fp32/int32 VMEM
  scratch accumulator revisited by a K-innermost grid; the output block is
  written exactly once, after the full reduction (Algorithm 1 lines 1/8).
* "Four-Z-register grouped loads"  ->  BlockSpec minor dims chosen by the
  analytic planner so every DMA row is >= 512 contiguous bytes.
* "On-the-fly transposition"  ->  ``dot_general`` dimension numbers contract
  whichever axis the stored layout dictates; no materialized transpose pass.
* "Predicated edge micro-kernels"  ->  K-remainder masking with iota
  predicates in-kernel; M/N edges use Pallas partial-block masked stores.
* "Mixed precision FMOPA"  ->  bf16 x bf16 -> f32 and int8 x int8 -> int32
  via ``preferred_element_type``, with the registry-driven fused epilogue
  (``core/gemm_spec.py``): dequant/alpha/bias/activation plus the gated-
  activation and residual-add fusions, all riding the accumulator's single
  store — the paper's first-round-online-packing lesson: never run a
  separate memory pass for work that can ride the GEMM.

:func:`make_gemm_kernel` is the single factory — 2-D vs grouped, dense vs
packed B, and every registered epilogue are spec parameters of ONE body,
not separate kernels.  :func:`mpgemm_pallas` / :func:`mpgemm_grouped_pallas`
are thin argument-to-spec adapters kept as the public entry points.

:func:`mpgemm_ragged_pallas` is the one launch beside the factory: a
grouped GEMM over ONE row buffer sorted by group (the served MoE step's
expert-sorted tokens), whose grid walks only the buffer's live row tiles
and DMAs each group's weights once per run of its tiles.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.blocking import (
    GemmPlan, grouped_plan_from_2d, plan_gemm, plan_with_blocks,
)
from repro.core.gemm_spec import (
    EpilogueSpec, GemmSpec, apply_epilogue, get_epilogue, resolve_epilogue,
)
from repro.packing.layout import PackedOperand, is_packed
from repro.packing.pack import unpack_nibbles
from repro.sparse.layout import TileSparseOperand, build_schedule, is_sparse


def resolve_b_operand(
    name: str,
    b,
    b_packed: Optional[PackedOperand] = None,
    b_sparse: Optional[TileSparseOperand] = None,
    *,
    stacklevel: int = 3,
):
    """Collapse the legacy ``b_packed=``/``b_sparse=`` keywords into the
    polymorphic ``b`` operand.

    Returns a normalized ``(b, b_packed, b_sparse)`` triple with exactly one
    entry set, dispatched on the OPERAND'S TYPE (dense array / PackedOperand
    / TileSparseOperand) rather than on which keyword carried it.  Passing
    the operand through ``b_packed=``/``b_sparse=`` still works but emits a
    DeprecationWarning — the keywords survive only as migration shims.
    """
    if sum(x is not None for x in (b, b_packed, b_sparse)) != 1:
        raise ValueError("exactly one of b / b_packed / b_sparse is required")
    if b_packed is not None or b_sparse is not None:
        kw = "b_packed" if b_packed is not None else "b_sparse"
        obs.warn_deprecated(
            f"{name}.{kw}",
            f"{name}({kw}=...) is deprecated; pass the operand as the "
            "polymorphic `b` argument (dispatch is by operand type)",
            stacklevel=stacklevel)
    op = b if b is not None else b_packed if b_packed is not None else b_sparse
    if is_packed(op):
        return None, op, None
    if is_sparse(op):
        return None, None, op
    return op, None, None


def _mask_contract(x, axis: int, valid):
    """Zero out lanes >= ``valid`` along ``axis`` (edge predication).

    The select runs in f32 (exact for every operand dtype here): Mosaic
    cannot select on packed 8/16-bit tiles with fewer rows than one
    sublane tile, which decode-sized blocks have."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx < valid, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _dot_dims(trans_a: bool, trans_b: bool):
    """dot_general dimension numbers for on-the-fly transposition.

    a block is stored (bm,bk) or, transposed, (bk,bm); likewise b is (bk,bn)
    or (bn,bk).  The contracting axis in the *stored* layout:
    """
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return (((ca,), (cb,)), ((), ()))


def _accumulate(acc_ref, a, b, ts, trans_a: bool, trans_b: bool, acc_dtype):
    """One K-step FMA into the resident accumulator.

    ``ts`` is the packed payload's per-tile dequant scale (None on the
    unpacked path).  With a per-tile scale the accumulator is f32 and the
    scale is applied per K step:

    * int x int (int8/int4-decoded payload vs an int8-quantized A): dot in
      int32, scale on the way into the f32 accumulator;
    * float A x quantized payload: dequantize the tile in VMEM before the
      dot (int8/fp8 HBM reads, upcast at the compute unit);
    * int A x FLOAT payload (activation-quantized X over an fp8 tile): no
      mixed int x float dot exists — both sides upcast to f32.
    """
    if ts is None:
        acc_ref[...] += jax.lax.dot_general(
            a, b, _dot_dims(trans_a, trans_b),
            preferred_element_type=acc_dtype)
        return
    a_int = jnp.issubdtype(a.dtype, jnp.integer)
    b_int = jnp.issubdtype(b.dtype, jnp.integer)
    if a_int and b_int:
        part = jax.lax.dot_general(
            a, b, _dot_dims(trans_a, trans_b),
            preferred_element_type=jnp.int32)
        acc_ref[...] += part.astype(jnp.float32) * ts
    else:
        bf = b.astype(jnp.float32) * ts
        af = a.astype(jnp.float32) if a_int else a
        if not a_int:
            bf = bf.astype(a.dtype)
        acc_ref[...] += jax.lax.dot_general(
            af, bf, _dot_dims(trans_a, trans_b),
            preferred_element_type=acc_dtype)


def make_gemm_kernel(*, spec: GemmSpec, epilogue: EpilogueSpec, nk: int,
                     k_rem: int, acc_dtype,
                     b_codec: Optional[str] = None,
                     b_rows: Optional[int] = None):
    """THE kernel factory: emit one Pallas body from the spec.

    ``b_codec``/``b_rows`` select an in-register payload decode for
    sub-byte packed B tiles (``int4``): the DMA'd (ceil(bk/2), bn) nibble
    tile is unpacked to ``b_rows`` int8 K rows right after the read, so
    the dequant rides the accumulation — no separate unpack launch ever
    exists (the paper's never-run-a-separate-memory-pass rule applied to
    the precision ladder).

    Grid = (M/bm, N/bn, K/bk) — grouped specs prepend the group axis G —
    with K innermost ('arbitrary').  Ref order (presence driven by the
    spec/epilogue): a, b, [tile_scales], [c], [bias], [scale],
    *epilogue-extras, out, acc-scratch.  Grouped block refs carry a size-1
    leading group dim; the accumulator scratch does not (it is recycled
    across groups because K is the only revisiting axis).

    **Tile-sparse specs** (``spec.sparse``) swap the dense K axis for a
    walk over the operand's stored-tile schedule: grid = (M/bm,
    schedule_len), the scalar-prefetched schedule arrays (kk, jj, slot,
    first, last[, gg]) lead the ref list, and the accumulator
    initializes/stores on the schedule's per-column first/last flags
    instead of ``kk == 0`` / ``kk == nk - 1`` — zero tiles are never
    visited.  ``nk`` is then the dense k-tile count (for K-tail
    predication via the prefetched ``kk``), and the grid never prepends a
    group axis (grouping is folded into the schedule).
    """
    ep_def = get_epilogue(epilogue.kind)
    grouped = spec.grouped
    k_axis = 3 if grouped else 2
    n_lead = 1 if grouped else 0  # size-1 group dim on every block ref

    def _read(ref, extra_lead: int = 0):
        lead = n_lead + extra_lead
        return ref[(0,) * lead] if lead else ref[...]

    def sparse_kernel(*refs):
        refs = list(refs)
        kk_ref = refs.pop(0)
        refs.pop(0)  # jj: consumed by the index maps only
        refs.pop(0)  # slot: consumed by the index maps only
        first_ref = refs.pop(0)
        last_ref = refs.pop(0)
        if grouped:
            refs.pop(0)  # gg: consumed by the index maps only
        a_ref = refs.pop(0)
        b_ref = refs.pop(0)
        ts_ref = refs.pop(0) if spec.tile_scaled else None
        c_ref = refs.pop(0) if epilogue.beta != 0.0 else None
        bias_ref = refs.pop(0) if epilogue.has_bias else None
        scale_ref = refs.pop(0) if epilogue.has_scale else None
        extra_refs = [refs.pop(0) for _ in ep_def.extra_operands]
        out_ref = refs.pop(0)
        acc_ref = refs.pop(0)

        t = pl.program_id(1)

        @pl.when(first_ref[t] == 1)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = _read(a_ref)
        b = b_ref[0]  # payload tile (1, bk, bn) -> (bk, bn)
        if k_rem:
            # The K-tail tile can appear ANYWHERE in the schedule; the
            # prefetched kk identifies it.  Payload tiles were zero-padded
            # at sparsify time, so only A needs the predicate.
            valid = jnp.where(kk_ref[t] == nk - 1, k_rem,
                              a.shape[0 if spec.trans_a else 1])
            a = _mask_contract(a, 0 if spec.trans_a else 1, valid)
        ts = ts_ref[0] if spec.tile_scaled else None
        _accumulate(acc_ref, a, b, ts, spec.trans_a, False, acc_dtype)

        @pl.when(last_ref[t] == 1)
        def _epilogue():
            out = apply_epilogue(
                epilogue, acc_ref[...],
                bias=_read(bias_ref) if bias_ref is not None else None,
                scale=scale_ref[0] if scale_ref is not None else None,
                c=_read(c_ref) if c_ref is not None else None,
                extras=tuple(_read(r) for r in extra_refs),
            ).astype(out_ref.dtype)
            out_ref[...] = out[None] if grouped else out

    if spec.sparse:
        return sparse_kernel

    def kernel(*refs):
        refs = list(refs)
        a_ref = refs.pop(0)
        b_ref = refs.pop(0)
        ts_ref = refs.pop(0) if spec.tile_scaled else None
        c_ref = refs.pop(0) if epilogue.beta != 0.0 else None
        bias_ref = refs.pop(0) if epilogue.has_bias else None
        scale_ref = refs.pop(0) if epilogue.has_scale else None
        extra_refs = [refs.pop(0) for _ in ep_def.extra_operands]
        out_ref = refs.pop(0)
        acc_ref = refs.pop(0)

        kk = pl.program_id(k_axis)

        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = _read(a_ref)
        # Packed B: the payload block is a pre-transposed, zero-padded
        # physical tile behind leading (1, 1) tile indices — an identity
        # index map, no strided DMA, no on-the-fly transposition.
        b = _read(b_ref, 2 if spec.packed else 0)
        if b_codec is not None:
            # Sub-byte payload: two K-adjacent nibbles per byte — unpack
            # the register tile to b_rows int8 K rows (zero-padded rows
            # decode to zero, so K-tail predication stays A-side only).
            b = unpack_nibbles(b, b_rows)
        if k_rem:
            # Paper's predicate registers: mask the K tail so pipeline pad
            # garbage (possibly NaN) never pollutes the accumulator.
            # Packed payload tiles were zero-padded at pack time, so only
            # A needs the predicate on that path.
            valid = jnp.where(kk == nk - 1, k_rem,
                              a.shape[0 if spec.trans_a else 1])
            a = _mask_contract(a, 0 if spec.trans_a else 1, valid)
            if not spec.packed:
                b = _mask_contract(b, 1 if spec.trans_b else 0, valid)

        ts = _read(ts_ref, 2) if spec.tile_scaled else None
        _accumulate(acc_ref, a, b, ts, spec.trans_a, spec.trans_b, acc_dtype)

        @pl.when(kk == nk - 1)
        def _epilogue():
            out = apply_epilogue(
                epilogue, acc_ref[...],
                bias=_read(bias_ref) if bias_ref is not None else None,
                scale=scale_ref[0] if scale_ref is not None else None,
                c=_read(c_ref) if c_ref is not None else None,
                extras=tuple(_read(r) for r in extra_refs),
            ).astype(out_ref.dtype)
            out_ref[...] = out[None] if grouped else out

    return kernel


def _compiler_params(grid_rank: int):
    """Grid semantics: every axis parallel except the K-innermost one."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1) + ("arbitrary",))


def _layout_plan(m: int, k: int, n: int, layout, a_dtype, out_dtype,
                 trans_a: bool, beta: float, *, sparse: bool, g: int = 1,
                 epilogue_tag: str = "", extra_mn: int = 0) -> GemmPlan:
    """Resolve a plan for a layout-pinned B operand (packed OR tile-sparse).

    ONE resolver for both pre-laid-out forms: tuned plan from the layout's
    namespace (``make_key(layout=tag)`` for packed, ``make_key(sparsity=
    tag)`` for sparse) if its blocks agree with the payload layout, else
    the analytic solve with (bn, bk) pinned to the layout — the payload's
    tiling IS the block decision, only bm stays free.  Sparse layouts
    additionally DENSITY-PRICE the analytic traffic/FLOP model (skipped
    tiles cost neither B bytes nor MACs — core/blocking.py ``density=``).
    Per-tile-scaled payloads force an f32 accumulator (scales vary per K
    step, so int32 accumulation across blocks is no longer exact)."""
    from repro.tuning.plan_cache import (
        lookup_plan, make_key, note_analytic_fallback,
    )
    acc = "float32" if layout.per_tile_scales else None
    density = layout.density if sparse else 1.0
    namespace = {"sparsity": layout.tag} if sparse else {"layout": layout.tag}
    plan = lookup_plan(
        m, n, k, a_dtype, layout.dtype, out_dtype,
        trans_a=trans_a, trans_b=False, beta=beta, g=g,
        epilogue=epilogue_tag, analytic_memo=True, **namespace,
    )
    if plan is not None and (plan.bn, plan.bk) != (layout.bn, layout.bk):
        plan = None  # tuned entry from a different payload tiling
    if plan is None:
        base = plan_gemm(m, n, k, a_dtype, layout.dtype,
                         out_dtype=out_dtype, acc_dtype=acc, beta=beta,
                         extra_mn_inputs=extra_mn, density=density)
        plan = plan_with_blocks(
            m, n, k, base.bm, layout.bn, layout.bk, a_dtype, layout.dtype,
            out_dtype, acc, beta=beta, extra_mn_inputs=extra_mn,
            density=density, notes="tile-sparse" if sparse else "packed-b",
        )
        if g != 1:
            plan = grouped_plan_from_2d(plan, g)
        note_analytic_fallback(make_key(
            m, n, k, a_dtype, layout.dtype, out_dtype,
            trans_a=trans_a, trans_b=False, beta=beta, g=g,
            epilogue=epilogue_tag, **namespace), plan)
    if layout.per_tile_scales and plan.acc_dtype != "float32":
        plan = dataclasses.replace(plan, acc_dtype="float32")
    return plan


def _bias_input(bias, grouped: bool, g: int, n: int):
    """Normalize a bias operand for the kernel's (1, bn) block reads:
    (N,)/(G, N) -> (1, N) or broadcast (G, 1, N) — shared by the dense and
    sparse launch paths."""
    if grouped:
        return jnp.broadcast_to(
            bias.reshape((1, -1) if bias.ndim == 1 else (g, -1))[:, None, :],
            (g, 1, n))
    return bias.reshape(1, -1)


def _scale_spec_and_input(scale, interpret: bool):
    """The dynamic-quant per-tensor scale rides SMEM (1-elem f32)."""
    spec = pl.BlockSpec(memory_space=None if interpret else pltpu.SMEM)
    return spec, jnp.asarray(scale, jnp.float32).reshape(1)


def _resolve_epilogue(activation, alpha, beta, bias, scale, gate, residual):
    """Build the EpilogueSpec + ordered extras tuple from wrapper kwargs
    (the shared registry-driven resolution — core/gemm_spec.py)."""
    return resolve_epilogue(
        {"gate": gate, "residual": residual},
        activation=activation, alpha=alpha, beta=beta,
        has_bias=bias is not None, has_scale=scale is not None,
    )


def _launch_sparse(a, b_sparse: TileSparseOperand, *, c, bias, scale, extras,
                   spec: GemmSpec, epilogue: EpilogueSpec, plan: GemmPlan,
                   out_dtype, acc_dtype, m: int, n: int, g: int,
                   interpret: bool):
    """Launch the tile-sparse walk: grid (M/bm, schedule_len).

    The dense K axis is replaced by the operand's stored-tile schedule;
    every BlockSpec index map reads the scalar-prefetched schedule arrays
    (kk = A-side k-tile, jj = output column, slot = payload tile, gg =
    group), so each grid step DMAs exactly one stored tile — zero tiles
    appear in neither the grid nor the DMA stream.  Grouped operands fold
    the group axis into the schedule (the grid stays rank 2); empty output
    columns get one anchor visit of the shared zero payload tile so their
    epilogue (bias/activation/residual/beta·C) still runs.
    """
    layout = b_sparse.layout
    grouped = spec.grouped
    sched = build_schedule(layout)
    t_len = layout.schedule_len
    bm, bn, bk = plan.bm, layout.bn, layout.bk
    grid = (pl.cdiv(m, bm), t_len)
    lead = (1,) if grouped else ()
    n_sp = 6 if grouped else 5  # kk, jj, slot, first, last [, gg]

    def _sim(f):
        """Index map over (i, t) + the scalar-prefetch refs; ``f`` gets
        (i, t, kk, jj, slot, gg)."""
        if grouped:
            return lambda i, t, kk, jj, slot, fr, la, gg: \
                f(i, t, kk, jj, slot, gg)
        return lambda i, t, kk, jj, slot, fr, la: \
            f(i, t, kk, jj, slot, None)

    def _lead(gg, t):
        return (gg[t],) if grouped else ()

    a_spec = (
        pl.BlockSpec(lead + (bk, bm),
                     _sim(lambda i, t, kk, jj, slot, gg:
                          _lead(gg, t) + (kk[t], i)))
        if spec.trans_a
        else pl.BlockSpec(lead + (bm, bk),
                          _sim(lambda i, t, kk, jj, slot, gg:
                               _lead(gg, t) + (i, kk[t])))
    )
    b_spec = pl.BlockSpec((1, bk, bn),
                          _sim(lambda i, t, kk, jj, slot, gg:
                               (slot[t], 0, 0)))
    in_specs = [a_spec, b_spec]
    inputs = [a, b_sparse.payload]
    if spec.tile_scaled:
        in_specs.append(pl.BlockSpec(
            (1, 1, 1), _sim(lambda i, t, kk, jj, slot, gg: (slot[t], 0, 0))))
        inputs.append(b_sparse.scales[..., None])
    mn_spec = pl.BlockSpec(
        lead + (bm, bn),
        _sim(lambda i, t, kk, jj, slot, gg: _lead(gg, t) + (i, jj[t])))
    if epilogue.beta != 0.0:
        in_specs.append(mn_spec)
        inputs.append(c)
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            lead + (1, bn),
            _sim(lambda i, t, kk, jj, slot, gg: _lead(gg, t) + (0, jj[t]))))
        inputs.append(_bias_input(bias, grouped, g, n))
    if scale is not None:
        sspec, scale1d = _scale_spec_and_input(scale, interpret)
        in_specs.append(sspec)
        inputs.append(scale1d)
    ep_def = get_epilogue(epilogue.kind)
    row_spec = pl.BlockSpec(
        lead + (bm, 1),
        _sim(lambda i, t, kk, jj, slot, gg: _lead(gg, t) + (i, 0)))
    for nm, x in zip(ep_def.extra_operands, extras):
        in_specs.append(row_spec if nm in ep_def.row_operands else mn_spec)
        inputs.append(x)

    kernel = make_gemm_kernel(
        spec=spec, epilogue=epilogue, nk=layout.nkb, k_rem=plan.k_rem,
        acc_dtype=acc_dtype,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_sp,
        grid=grid,
        in_specs=in_specs,
        out_specs=mn_spec,
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
    )
    sp_args = [jnp.asarray(x) for x in
               (sched.kk, sched.jj, sched.slot, sched.first, sched.last)]
    if grouped:
        sp_args.append(jnp.asarray(sched.gg))
    out_shape = ((g, m, n) if grouped else (m, n))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        name="mpgemm_grouped" if grouped else "mpgemm",
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(*sp_args, *inputs)


def mpgemm_pallas_spec(
    a: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    b_packed: Optional[PackedOperand] = None,
    b_sparse: Optional[TileSparseOperand] = None,
    c: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[jax.Array] = None,
    extras: Sequence[jax.Array] = (),
    spec: GemmSpec,
    epilogue: EpilogueSpec = EpilogueSpec(),
    out_dtype=None,
    plan: Optional[GemmPlan] = None,
    interpret: bool = False,
) -> jax.Array:
    """Launch ONE spec-described GEMM through the kernel factory.

    The single launch path behind :func:`mpgemm_pallas` and
    :func:`mpgemm_grouped_pallas` (and the op layer's custom-VJP core):
    resolves shapes, plan (tuned cache -> analytic fallback, keyed with the
    epilogue tag so fused and unfused tunings never collide), BlockSpecs,
    and the kernel body — one accumulator / edge-predication / epilogue
    implementation for all spec combinations.  ``b_sparse`` selects the
    tile-sparse walk: the grid's K axis is replaced by the operand's
    stored-tile schedule (scalar-prefetched index maps), so pruned tiles
    are never DMA'd or multiplied.
    """
    grouped = spec.grouped
    b, b_packed, b_sparse = resolve_b_operand(
        "mpgemm_pallas_spec", b, b_packed, b_sparse)
    layout = b_packed.layout if b_packed is not None else None
    slayout = b_sparse.layout if b_sparse is not None else None
    # Normalize packed/sparse/tile_scaled from the ACTUAL operand, not the
    # caller's spec: a default-constructed spec over a per-tile-scaled
    # payload must still stream the scales (silently skipping the dequant
    # would return wrong numerics with no error).
    spec = dataclasses.replace(
        spec, packed=layout is not None, sparse=slayout is not None,
        tile_scaled=(layout is not None and layout.per_tile_scales)
        or (slayout is not None and slayout.per_tile_scales))
    b_layout = layout if layout is not None else slayout
    if layout is not None and not layout.kernel_native:
        raise NotImplementedError(
            f"payload codec {layout.dtype!r} is bit-emulated on this "
            "install; use the XLA unpack path (packing.pack.unpack_operand)")
    if b_layout is not None:
        if grouped and b_layout.g == 1:
            raise ValueError("2-D payload: use a non-grouped spec")
        if not grouped and b_layout.g != 1:
            raise ValueError("grouped payload: use a grouped spec")
    if grouped:
        if a.ndim != 3 or (b is not None and b.ndim != 3):
            raise ValueError(
                f"grouped operands must be rank-3: got a={a.shape}")
        g = a.shape[0]
        if b_layout is not None and b_layout.g != g:
            raise ValueError(
                f"group mismatch: a has {g}, payload {b_layout.g}")
        if b is not None and b.shape[0] != g:
            raise ValueError(f"group mismatch: {a.shape} x {b.shape}")
        m = a.shape[2] if spec.trans_a else a.shape[1]
        ka = a.shape[1] if spec.trans_a else a.shape[2]
    else:
        g = 1
        m = a.shape[1] if spec.trans_a else a.shape[0]
        ka = a.shape[0] if spec.trans_a else a.shape[1]
    if b_layout is not None:
        n, kb = b_layout.n, b_layout.k
    elif grouped:
        n = b.shape[1] if spec.trans_b else b.shape[2]
        kb = b.shape[2] if spec.trans_b else b.shape[1]
    else:
        n = b.shape[0] if spec.trans_b else b.shape[1]
        kb = b.shape[1] if spec.trans_b else b.shape[0]
    if ka != kb:
        bshape = (b_layout.payload_shape if b_layout is not None
                  else b.shape)
        raise ValueError(f"contraction mismatch: {a.shape} x {bshape}")
    k = ka

    # Normalize the epilogue to operand presence (the factory keys ref
    # unpacking off these flags).
    epilogue = dataclasses.replace(
        epilogue, has_bias=bias is not None, has_scale=scale is not None)
    ep_def = get_epilogue(epilogue.kind)
    extras = tuple(extras)
    if len(extras) != len(ep_def.extra_operands):
        raise ValueError(
            f"epilogue {epilogue.kind!r} needs operands "
            f"{ep_def.extra_operands}, got {len(extras)}")
    if epilogue.beta != 0.0 and c is None:
        raise ValueError("beta != 0 requires c")
    # (M, 1) row-scale extras stream (bm, 1) blocks — only the (M, N)-shaped
    # ones price as full output-sized inputs in the traffic model.
    n_extra_mn = sum(1 for nm in ep_def.extra_operands
                     if nm not in ep_def.row_operands)

    # --- plan resolution: explicit > tuned (epilogue-tagged) > analytic ---
    if plan is not None and b_layout is not None and (
            (plan.bn, plan.bk) != (b_layout.bn, b_layout.bk)):
        raise ValueError(
            f"plan blocks ({plan.bn}, {plan.bk}) incompatible with "
            f"packed/sparse layout ({b_layout.bn}, {b_layout.bk})")
    with obs.span("gemm.plan", m=m, n=n, k=k, g=g):
        if plan is None and b_layout is not None:
            plan = _layout_plan(m, k, n, b_layout, a.dtype, out_dtype,
                                spec.trans_a, epilogue.beta,
                                sparse=slayout is not None, g=g,
                                epilogue_tag=epilogue.tag,
                                extra_mn=n_extra_mn)
        if plan is None:
            # Closed-loop planning: a tuned plan from the persistent cache
            # wins over the analytic model (repro.tuning populates it; lazy
            # import keeps the kernel layer free of a hard tuning
            # dependency).
            from repro.tuning.plan_cache import lookup_plan
            plan = lookup_plan(
                m, n, k, a.dtype, b.dtype, out_dtype,
                trans_a=spec.trans_a, trans_b=spec.trans_b,
                beta=epilogue.beta, g=g, epilogue=epilogue.tag,
                analytic_memo=True,
            )
        if plan is None:
            from repro.tuning.plan_cache import (
                make_key, note_analytic_fallback,
            )
            plan = plan_gemm(
                m, n, k, a.dtype, b.dtype, out_dtype=out_dtype,
                beta=epilogue.beta, extra_mn_inputs=n_extra_mn,
            )
            if grouped:
                plan = grouped_plan_from_2d(plan, g)
            note_analytic_fallback(make_key(
                m, n, k, a.dtype, b.dtype, out_dtype,
                trans_a=spec.trans_a, trans_b=spec.trans_b,
                beta=epilogue.beta, g=g, epilogue=epilogue.tag), plan)
        obs.annotate(bytes=plan.hbm_bytes, flops=plan.flops, cmr=plan.cmr)
    out_dtype = jnp.dtype(out_dtype or plan.out_dtype)
    acc_dtype = jnp.dtype(plan.acc_dtype)
    if b_layout is not None and b_layout.per_tile_scales:
        # Per-tile scales accumulate scaled f32 partials — coerce even for
        # an explicitly supplied plan (mirrors _layout_plan; an int32
        # accumulator would reject the scaled stores deep inside Pallas).
        acc_dtype = jnp.dtype(jnp.float32)
    # Per-spec launch accounting: one series per (layout, codec, epilogue,
    # sparse, grouped) combination — the runtime census of which kernel
    # variants a workload actually exercises (counted at trace time, like
    # every other jaxpr-level fact in this stack).
    launch_labels = dict(
        layout=("packed" if layout is not None
                else "sparse" if slayout is not None else "dense"),
        codec=(b_layout.dtype if b_layout is not None else "none"),
        epilogue=epilogue.kind,
        sparse=str(slayout is not None).lower(),
        grouped=str(grouped).lower(),
    )
    obs.counter_inc("gemm_launches_total",
                    help="GEMM launches by spec combination",
                    **launch_labels)
    if spec.sparse:
        with obs.span("gemm.launch", bytes=plan.hbm_bytes,
                      flops=plan.flops, m=m, n=n, k=k, g=g,
                      **launch_labels):
            return _launch_sparse(
                a, b_sparse, c=c, bias=bias, scale=scale, extras=extras,
                spec=spec, epilogue=epilogue, plan=plan, out_dtype=out_dtype,
                acc_dtype=acc_dtype, m=m, n=n, g=g, interpret=interpret)
    bm, bn, bk = plan.bm, plan.bn, plan.bk
    grid = ((g,) if grouped else ()) + (
        pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))

    # --- BlockSpecs: grouped specs prepend a size-1 group block dim and a
    # leading group index to every map -----------------------------------
    lead = (1,) if grouped else ()

    def _im(f):
        if grouped:
            return lambda gg, i, j, kk: (gg,) + f(i, j, kk)
        return lambda i, j, kk: f(i, j, kk)

    a_spec = (
        pl.BlockSpec(lead + (bk, bm), _im(lambda i, j, kk: (kk, i)))
        if spec.trans_a
        else pl.BlockSpec(lead + (bm, bk), _im(lambda i, j, kk: (i, kk)))
    )
    if layout is not None:
        # Identity tile read: grid step (i, j, kk) fetches payload tile
        # (kk, j) — one contiguous DMA, the payoff of ahead-of-time packing.
        # The block minor dims are the PHYSICAL payload tile (sub-byte
        # codecs store ceil(bk/2) nibble-pair rows per logical bk).
        b_spec = pl.BlockSpec(lead + (1, 1) + layout.payload_tile,
                              _im(lambda i, j, kk: (kk, j, 0, 0)))
        inputs = [a, b_packed.payload]
    else:
        b_spec = (
            pl.BlockSpec(lead + (bn, bk), _im(lambda i, j, kk: (j, kk)))
            if spec.trans_b
            else pl.BlockSpec(lead + (bk, bn), _im(lambda i, j, kk: (kk, j)))
        )
        inputs = [a, b]
    in_specs = [a_spec, b_spec]
    if spec.tile_scaled:
        # One scale per (kk, j) tile, carried as a trailing (1, 1) so the
        # block's minor dims equal the array's (Mosaic's tiling rule).
        in_specs.append(pl.BlockSpec(lead + (1, 1, 1, 1),
                                     _im(lambda i, j, kk: (kk, j, 0, 0))))
        inputs.append(b_packed.scales[..., None, None])
    mn_spec = pl.BlockSpec(lead + (bm, bn), _im(lambda i, j, kk: (i, j)))
    if epilogue.beta != 0.0:
        in_specs.append(mn_spec)
        inputs.append(c)
    if bias is not None:
        in_specs.append(pl.BlockSpec(lead + (1, bn),
                                     _im(lambda i, j, kk: (0, j))))
        inputs.append(_bias_input(bias, grouped, g, n))
    if scale is not None:
        sspec, scale1d = _scale_spec_and_input(scale, interpret)
        in_specs.append(sspec)
        inputs.append(scale1d)
    row_spec = pl.BlockSpec(lead + (bm, 1), _im(lambda i, j, kk: (i, 0)))
    for nm, x in zip(ep_def.extra_operands, extras):
        in_specs.append(row_spec if nm in ep_def.row_operands else mn_spec)
        inputs.append(x)

    codec = layout.codec if layout is not None else None
    sub_byte = codec is not None and codec.elems_per_byte > 1
    kernel = make_gemm_kernel(
        spec=spec,
        epilogue=epilogue,
        nk=grid[-1],
        k_rem=plan.k_rem,
        acc_dtype=acc_dtype,
        b_codec=codec.name if sub_byte else None,
        b_rows=layout.bk if sub_byte else None,
    )

    out_shape = ((g, m, n) if grouped else (m, n))
    with obs.span("gemm.launch", bytes=plan.hbm_bytes, flops=plan.flops,
                  m=m, n=n, k=k, g=g, grid=str(grid), **launch_labels):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=mn_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
            name="mpgemm_grouped" if grouped else "mpgemm",
            compiler_params=_compiler_params(len(grid)),
            interpret=interpret,
        )(*inputs)


# --- public wrappers (argument -> spec adapters) -----------------------------

def mpgemm_pallas(
    a: jax.Array,
    b: Optional[jax.Array] = None,
    c: Optional[jax.Array] = None,
    *,
    b_packed: Optional[PackedOperand] = None,
    b_sparse: Optional[TileSparseOperand] = None,
    trans_a: bool = False,
    trans_b: bool = False,
    alpha: float = 1.0,
    beta: float = 0.0,
    bias: Optional[jax.Array] = None,
    scale: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    out_dtype=None,
    plan: Optional[GemmPlan] = None,
    interpret: bool = False,
) -> jax.Array:
    """out = tail(alpha * op(a) @ op(b) * scale + bias) + beta * c.

    ``tail`` is the registry epilogue: ``activation`` alone selects the
    linear family; ``gate`` selects the gated fusion (``act(acc) · gate``,
    the SwiGLU/GeGLU step in one launch); ``residual`` the residual-add
    fusion (``act(acc) + residual``).  ``gate``/``residual`` are (M, N)
    operands streamed per output block.

    ``b_packed`` replaces ``b`` with a pre-packed operand (repro.packing):
    the kernel reads the (bk, bn)-tiled payload through identity index
    maps — no strided DMA, no on-the-fly transposition (it was resolved at
    pack time), and for int8 payloads the per-tile dequant rides the
    accumulation.  ``b_sparse`` replaces ``b`` with a tile-sparse operand
    (repro.sparse): only the stored tiles are visited — the grid's K axis
    becomes the stored-tile schedule, steered by scalar-prefetched index
    maps.  ``b``/``b_packed``/``b_sparse`` are mutually exclusive, and the
    pre-packed forms exclude ``trans_b`` (resolved at pack/sparsify time).
    """
    b, b_packed, b_sparse = resolve_b_operand(
        "mpgemm_pallas", b, b_packed, b_sparse)
    layout = (b_packed.layout if b_packed is not None
              else b_sparse.layout if b_sparse is not None else None)
    if layout is not None and layout.g != 1:
        raise ValueError("grouped payload: use mpgemm_grouped_pallas")
    epilogue, extras = _resolve_epilogue(
        activation, alpha, beta, bias, scale, gate, residual)
    spec = GemmSpec(
        grouped=False,
        packed=b_packed is not None,
        sparse=b_sparse is not None,
        tile_scaled=layout is not None and layout.per_tile_scales,
        trans_a=trans_a,
        trans_b=False if layout is not None else trans_b,
    )
    op = (b if b is not None
          else b_packed if b_packed is not None else b_sparse)
    return mpgemm_pallas_spec(
        a, op, c=c, bias=bias,
        scale=scale, extras=extras, spec=spec, epilogue=epilogue,
        out_dtype=out_dtype, plan=plan, interpret=interpret,
    )


def mpgemm_grouped_pallas(
    a: jax.Array,
    b: Optional[jax.Array] = None,
    c: Optional[jax.Array] = None,
    *,
    b_packed: Optional[PackedOperand] = None,
    b_sparse: Optional[TileSparseOperand] = None,
    trans_a: bool = False,
    trans_b: bool = False,
    alpha: float = 1.0,
    beta: float = 0.0,
    bias: Optional[jax.Array] = None,
    scale: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    out_dtype=None,
    plan: Optional[GemmPlan] = None,
    interpret: bool = False,
) -> jax.Array:
    """out[g] = tail(alpha * op(a[g]) @ op(b[g]) * scale + bias[g]) + beta*c[g].

    ``a``: (G, M, K) — or (G, K, M) under ``trans_a``; ``b``: (G, K, N) —
    or (G, N, K) under ``trans_b``; ``bias``: (G, N) or (N,) broadcast to
    every group; ``gate``/``residual``/``c``: (G, M, N); output (G, M, N).
    The G expert/batch problems share one kernel launch with the group as
    the leading (parallel) grid axis, so small per-expert GEMMs amortize
    launch and pipeline ramp-up instead of paying them G times — the
    grouped-GEMM-on-SME pattern (LOHO, Hello SME!) in TPU form.  The same
    registry epilogues as :func:`mpgemm_pallas` apply per group (the
    spec-driven factory made the grouped beta·C term free).

    ``b_packed`` replaces ``b`` with a grouped packed operand (payload
    ``(G, nkb, nnb, bk, bn)``): identity tile reads per group, transpose
    resolved at pack time, per-tile int8 dequant riding the accumulation —
    the pre-packed-expert-weights serving configuration.  ``b_sparse``
    replaces ``b`` with a grouped tile-sparse operand: the per-expert
    sparsity patterns fold into one flat stored-tile schedule, so the
    launch walks exactly the union of every expert's nonzero tiles
    (pruned experts cost nothing — the tile-sparse MoE configuration).
    """
    b, b_packed, b_sparse = resolve_b_operand(
        "mpgemm_grouped_pallas", b, b_packed, b_sparse)
    layout = (b_packed.layout if b_packed is not None
              else b_sparse.layout if b_sparse is not None else None)
    if layout is not None and layout.g == 1:
        raise ValueError("2-D payload: use mpgemm_pallas")
    epilogue, extras = _resolve_epilogue(
        activation, alpha, beta, bias, scale, gate, residual)
    spec = GemmSpec(
        grouped=True,
        packed=b_packed is not None,
        sparse=b_sparse is not None,
        tile_scaled=layout is not None and layout.per_tile_scales,
        trans_a=trans_a,
        trans_b=False if layout is not None else trans_b,
    )
    op = (b if b is not None
          else b_packed if b_packed is not None else b_sparse)
    return mpgemm_pallas_spec(
        a, op, c=c, bias=bias,
        scale=scale, extras=extras, spec=spec, epilogue=epilogue,
        out_dtype=out_dtype, plan=plan, interpret=interpret,
    )


# --- ragged grouped launch: one group-sorted row buffer, live tiles only ----

# Scoped-VMEM bytes the double-buffered weight block of a ragged launch may
# take (of the 16 MiB limit; row tiles, gate and output blocks take the rest).
RAGGED_WEIGHT_VMEM = 8 * 2**20


class RaggedTiles(NamedTuple):
    """Row-tile map of a group-sorted ragged buffer of ``T`` tiles.

    ``group`` (T,) int32: the group whose weights each tile multiplies;
    ``fetch`` (T,) int32: at the first tile of a group's run, the next live
    group (whose weights the launch prefetches there), else -1;
    ``live`` (1,) int32: how many tiles hold rows, a prefix of the T.
    """
    group: jax.Array
    fetch: jax.Array
    live: jax.Array


def ragged_block_rows(rows: int, groups: int) -> int:
    """Row-tile height for ``rows`` rows over ``groups`` groups: the next
    power of two at or above the mean rows per group, within [8, 128]."""
    mean = -(-rows // groups)
    return min(128, max(8, 1 << max(0, mean - 1).bit_length()))


def ragged_rows(rows: int, groups: int) -> int:
    """Static size of a buffer holding ``rows`` rows with every group's
    segment starting on a tile of :func:`ragged_block_rows` rows: each
    group pads fewer than one tile."""
    bm = ragged_block_rows(rows, groups)
    return pl.cdiv(rows, bm) * bm + groups * bm


def ragged_layout(counts: jax.Array, bm: int, n_tiles: int):
    """-> (first row of each group's segment (G,), :class:`RaggedTiles`)
    for groups of ``counts`` rows laid out in group order, each segment
    padded to whole tiles of ``bm`` rows."""
    counts = counts.astype(jnp.int32)
    g = counts.shape[0]
    per = (counts + bm - 1) // bm
    ends = jnp.cumsum(per)
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(ends, t, side="right"),
                        g - 1).astype(jnp.int32)
    # The next group with rows after each group (g: none).
    ids = jnp.where(counts > 0, jnp.arange(g, dtype=jnp.int32), g)
    after = jnp.append(jax.lax.cummin(ids, reverse=True)[1:], g)
    first = jnp.append(True, group[1:] != group[:-1])
    live = ends[-1]
    fetch = jnp.where(first & (t < live) & (after[group] < g),
                      after[group], -1).astype(jnp.int32)
    return (ends - per) * bm, RaggedTiles(group, fetch, live.reshape(1))


def _ragged_block_cols(k: int, n: int, itemsize: int) -> int:
    """Widest column block of a (K, N) weight whose two VMEM buffers fit
    :data:`RAGGED_WEIGHT_VMEM`: N itself, else a multiple of 128 lanes
    dividing N."""
    for bn in [n] + list(range(n - n % 128, 0, -128)):
        if n % bn == 0 and 2 * k * bn * itemsize <= RAGGED_WEIGHT_VMEM:
            return bn
    raise ValueError(f"no column block of a ({k}, {n}) weight fits "
                     f"{RAGGED_WEIGHT_VMEM} bytes of VMEM twice")


def _ragged_kernel(x_hbm, w_hbm, group_ref, fetch_ref, live_ref, layer_ref,
                   *refs, bm: int, bn: int, nb: int, gated: bool,
                   epilogue: EpilogueSpec, compute_dtype):
    """One column block of the ragged launch: a loop over the live tiles.

    Per tile: its rows (and gate rows) arrive by DMA while the previous
    tile computes; at the first tile of a group's run the group's weight
    block (of layer ``layer_ref[0]`` of the stacked weights) is waited for
    and the next live group's is started into the other buffer; the
    tile's product and epilogue are stored by a DMA that is waited for two
    tiles later.  Tiles past ``live`` are never touched.
    """
    if gated:
        g_hbm, o_hbm, xbuf, wbuf, gbuf, obuf, sems = refs
    else:
        o_hbm, xbuf, wbuf, obuf, sems = refs
    j = pl.program_id(0)
    n_live = live_ref[0]
    layer = layer_ref[0]

    def cols(ref):
        return ref if nb == 1 else ref.at[:, pl.ds(j * bn, bn)]

    def rows(ref, t):
        return ref.at[pl.ds(t * bm, bm)]

    def x_copy(t, slot):
        return pltpu.make_async_copy(rows(x_hbm, t), xbuf.at[slot],
                                     sems.at[0, slot])

    def g_copy(t, slot):
        return pltpu.make_async_copy(cols(rows(g_hbm, t)), gbuf.at[slot],
                                     sems.at[1, slot])

    def w_copy(grp, slot):
        return pltpu.make_async_copy(cols(w_hbm.at[layer, grp]),
                                     wbuf.at[slot],
                                     sems.at[2, slot])

    def o_copy(t, slot):
        return pltpu.make_async_copy(obuf.at[slot], cols(rows(o_hbm, t)),
                                     sems.at[3, slot])

    def fetch_rows(t, slot):
        x_copy(t, slot).start()
        if gated:
            g_copy(t, slot).start()

    @pl.when(n_live > 0)
    def _prologue():
        fetch_rows(0, 0)
        w_copy(group_ref[0], 0).start()

    def tile(t, wslot):
        slot = t % 2
        grp = group_ref[t]
        new_run = jnp.logical_and(t > 0, grp != group_ref[jnp.maximum(t - 1, 0)])
        wslot = jnp.where(new_run, 1 - wslot, wslot)

        @pl.when(t + 1 < n_live)
        def _next_rows():
            fetch_rows(t + 1, 1 - slot)

        @pl.when(jnp.logical_or(t == 0, new_run))
        def _weights():
            w_copy(grp, wslot).wait()
            nxt = fetch_ref[t]

            @pl.when(nxt >= 0)
            def _next_weights():
                w_copy(nxt, 1 - wslot).start()

        x_copy(t, slot).wait()
        acc = jax.lax.dot_general(
            xbuf[slot].astype(compute_dtype), wbuf[wslot],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        extras = ()
        if gated:
            g_copy(t, slot).wait()
            extras = (gbuf[slot],)
        out = apply_epilogue(epilogue, acc, extras=extras)

        @pl.when(t >= 2)
        def _store_done():
            o_copy(t - 2, slot).wait()

        obuf[slot] = out.astype(obuf.dtype)
        o_copy(t, slot).start()
        return wslot

    jax.lax.fori_loop(0, n_live, tile, jnp.int32(0))
    for back in (2, 1):
        @pl.when(n_live >= back)
        def _drain():
            t = n_live - back
            o_copy(t, t % 2).wait()


def mpgemm_ragged_pallas(
    x: jax.Array,
    w: jax.Array,
    tiles: RaggedTiles,
    *,
    activation: Optional[str] = None,
    gate: Optional[jax.Array] = None,
    layer=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """out[r] = tail(x[r] @ w[group of r's tile]) over the live row tiles.

    ``x``: (R, K) rows sorted by group, cut into the ``T`` tiles of
    ``tiles`` (``bm = R // T`` rows each, every group's rows starting on a
    tile); ``w``: (G, K, N) in the compute dtype (``x`` is cast to it in
    VMEM), or with ``layer`` (an int32 scalar) the (L, G, K, N) weights of
    a layer stack, of which the launch reads layer ``layer`` in place, so
    the caller's layer loop need not slice (copy) the whole stack;
    ``gate``: (R, N), selecting the gated epilogue ``act(acc) · gate``
    (registry, ``core/gemm_spec.py``).  Output rows of tiles past
    ``tiles.live`` are left unwritten.

    The grid runs over column blocks of N (one where two (K, N) weight
    blocks fit :data:`RAGGED_WEIGHT_VMEM`); inside, a loop walks the live
    tiles with every operand in HBM (``pl.ANY``) and moved by manual DMA:
    row tiles double-buffered, a group's weight block fetched once per run
    of its tiles and the next live group's fetched under the current
    run's products, stores in flight behind the next tile.  The row
    buffer is the first operand and the tile map and layer follow the
    data in SMEM, so no operand the launch reads before its rows is an
    int32 scalar-prefetch array.
    """
    if layer is None:
        w, layer = w[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    r, k = x.shape
    _, g, kw, n = w.shape
    n_tiles = tiles.group.shape[0]
    if kw != k or r % n_tiles:
        raise ValueError(f"ragged operands mismatch: x {x.shape}, w "
                         f"{w.shape}, {n_tiles} tiles")
    bm = r // n_tiles
    epilogue, extras = resolve_epilogue({"gate": gate},
                                        activation=activation)
    gated = gate is not None
    bn = _ragged_block_cols(k, n, jnp.dtype(w.dtype).itemsize)
    out_dtype = jnp.dtype(out_dtype)
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    smem = pl.BlockSpec(memory_space=None if interpret else pltpu.SMEM)
    scratch = [pltpu.VMEM((2, bm, k), x.dtype),
               pltpu.VMEM((2, k, bn), w.dtype)]
    if gated:
        scratch.append(pltpu.VMEM((2, bm, bn), gate.dtype))
    scratch += [pltpu.VMEM((2, bm, bn), out_dtype),
                pltpu.SemaphoreType.DMA((4, 2))]
    kernel = functools.partial(
        _ragged_kernel, bm=bm, bn=bn, nb=n // bn, gated=gated,
        epilogue=epilogue, compute_dtype=w.dtype)
    obs.counter_inc("gemm_launches_total",
                    help="GEMM launches by spec combination",
                    layout="dense", codec="none", epilogue=epilogue.kind,
                    sparse="false", grouped="ragged")
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[anyspace, anyspace, smem, smem, smem, smem]
        + [anyspace] * len(extras),
        out_specs=anyspace,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        scratch_shapes=scratch,
        name="mpgemm_ragged",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w, tiles.group, tiles.fetch, tiles.live, layer, *extras)
