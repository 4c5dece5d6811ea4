"""``mp_dot`` / ``mp_dot_grouped`` — the paper's technique as first-class,
differentiable ops over ONE spec-driven core.

Every matmul in every model in this framework flows through here — 2-D
projections through :func:`mp_dot`, grouped/batched contractions (MoE expert
GEMMs, per-stream LoRA blocks, generic batched matmuls) through
:func:`mp_dot_grouped`.  Both are thin adapters over a single
``jax.custom_vjp`` core that dispatches on a static
:class:`~repro.core.gemm_spec.GemmSpec` (2-D vs grouped, dense vs packed B,
transposition) plus an :class:`~repro.core.gemm_spec.EpilogueSpec`
(activation, gated-activation and residual-add fusions from the epilogue
registry).  The core:

* applies a :class:`PrecisionPolicy` (fp32 / bf16->f32 / dynamic int8->i32 —
  the paper's Section V multi-precision surface),
* dispatches to the spec-driven Pallas MPGEMM launch (TPU / interpret) —
  which consults the tuned-plan cache, keyed with the epilogue tag — or to
  an XLA ``dot_general`` with identical precision AND epilogue semantics
  (CPU dry-run; XLA picks its own tiling, so plans only affect the kernel
  backends),
* implements ONE VJP whose backward GEMMs use the **fused-transpose**
  kernel variants (dx = dz · Wᵀ, dW = Xᵀ · dz) — the training-time payoff of
  the paper's on-the-fly transposition: no transposed weight copies are ever
  materialized.  Epilogue fusions differentiate through the registry's
  backward rules (packed-int8 weights stay frozen via float0 cotangents;
  float payloads repack their dense cotangent; grouped backward keeps the
  fused-transpose grouped contractions).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import config as cfg
from repro.core.gemm_spec import (
    EpilogueSpec, GemmSpec, apply_epilogue, epilogue_bwd, epilogue_needs_pre,
    get_epilogue, resolve_epilogue,
)
from repro.core.policy import PrecisionPolicy, get_policy, quantize_per_tensor
from repro.kernels.mpgemm import mpgemm_pallas_spec
from repro.packing.layout import PackedOperand, is_packed
from repro.sparse.layout import TileSparseOperand, is_sparse

_LINEAR = EpilogueSpec()


def _dims(spec: GemmSpec):
    """dot_general dims for the XLA backend (grouped: group = batch axis)."""
    if spec.grouped:
        ca = 1 if spec.trans_a else 2
        cb = 2 if spec.trans_b else 1
        return (((ca,), (cb,)), ((0,), (0,)))
    ca = 0 if spec.trans_a else 1
    cb = 1 if spec.trans_b else 0
    return (((ca,), (cb,)), ((), ()))


def _xla_epilogue(epilogue, acc, bias, scale, extras, grouped):
    """The kernel's fused epilogue, re-played on a full XLA accumulator —
    same ``apply_epilogue`` implementation, so backends cannot drift."""
    if bias is not None:
        bias = (bias.reshape(bias.shape[0], 1, -1) if grouped
                else bias.reshape(1, -1))
    return apply_epilogue(epilogue, acc, bias=bias, scale=scale,
                          extras=extras)


def _note_xla_dispatch(x, w, spec, epilogue, ep_def, out_dtype):
    """Launch census + modeled-plan telemetry for GEMMs dispatched to XLA.

    Mirrors the kernel path's accounting in ``kernels/mpgemm.py`` so the
    per-spec launch counters and the plan-cache hit/miss series do not go
    dark on non-kernel backends (CPU serving, the explicit ``backend="xla"``
    A/B baseline).  XLA picks its own tiling, so the resolved plan is used
    for MODELING only (span bytes/FLOPs) and never steers the dispatch.
    Trace-time host code — a cached jit executable never re-enters it.
    """
    if not (obs.metrics_enabled() or obs.tracing_enabled()):
        return
    from repro.core.blocking import grouped_plan_from_2d, plan_gemm
    from repro.tuning.plan_cache import (
        lookup_plan, make_key, note_analytic_fallback,
    )
    g = x.shape[0] if spec.grouped else 1
    m = x.shape[-1] if spec.trans_a else x.shape[-2]
    k = x.shape[-2] if spec.trans_a else x.shape[-1]
    n = w.shape[-2] if spec.trans_b else w.shape[-1]
    n_extra_mn = sum(1 for nm in ep_def.extra_operands
                     if nm not in ep_def.row_operands)
    with obs.span("gemm.plan", m=m, n=n, k=k, g=g):
        plan = lookup_plan(
            m, n, k, x.dtype, w.dtype, out_dtype,
            trans_a=spec.trans_a, trans_b=spec.trans_b,
            beta=epilogue.beta, g=g, epilogue=epilogue.tag,
            analytic_memo=True)
        if plan is None:
            plan = plan_gemm(m, n, k, x.dtype, w.dtype, out_dtype=out_dtype,
                             beta=epilogue.beta, extra_mn_inputs=n_extra_mn)
            if spec.grouped:
                plan = grouped_plan_from_2d(plan, g)
            note_analytic_fallback(make_key(
                m, n, k, x.dtype, w.dtype, out_dtype,
                trans_a=spec.trans_a, trans_b=spec.trans_b,
                beta=epilogue.beta, g=g, epilogue=epilogue.tag), plan)
        obs.annotate(bytes=plan.hbm_bytes, flops=plan.flops, cmr=plan.cmr)
    obs.counter_inc("gemm_launches_total",
                    help="GEMM launches by spec combination",
                    layout="dense", codec="none", epilogue=epilogue.kind,
                    sparse="false", grouped=str(spec.grouped).lower())


def _apply_gemm(x, w, bias, extras, spec: GemmSpec, epilogue: EpilogueSpec,
                policy: PrecisionPolicy, backend: str, acc_dtype=None):
    """One GEMM under a policy on the selected backend — THE dispatch point.

    The single home of the policy logic for every spec combination
    (2-D/grouped × dense/packed × every registered epilogue):

    * Packed ``w`` (:class:`PackedOperand`): kernel backends read the
      payload directly — identity tile index maps, transpose resolved at
      pack time, per-tile int8 dequant riding the accumulation — so NO
      per-call operand prep (cast / dequant / strided re-layout) is
      materialized.  The XLA backend, which picks its own tiling, unpacks
      once and reuses the dense-path policy logic below.
    * ``w`` is a dense array or a :class:`PackedOperand` — NEVER a
      static-int8 {"q","scale"} dict: the differentiable wrappers
      dequantize dicts BEFORE the custom-VJP core (:func:`_dequant_static`)
      so dict primals never need dict cotangents, and XLA still fuses that
      dequant into the consuming GEMM read.
    * The compute-dtype down-cast is pinned shard-local BEFORE any
      FSDP/EP all-gather: without the barrier GSPMD gathers the f32 master
      weights and converts after, doubling gather wire bytes.  Safe under
      differentiation: it only ever runs inside the custom-VJP core, where
      JAX never needs a JVP rule for the barrier.
    * ``acc_dtype`` overrides the accumulator/partial-sum dtype on the XLA
      backend: backward GEMMs pass bf16 so that TP/EP partial-sum
      all-reduces move bf16 instead of f32 (halves gradient wire bytes).
      Kernel backends accumulate per the plan's acc dtype instead (plans
      own kernel numerics; f32/i32 VMEM scratch).
    """
    grouped = spec.grouped
    out_dtype = spec.out_dtype or policy.out_dtype
    kernel_backend = backend in ("pallas", "interpret")
    interp = backend == "interpret"

    # Registry pre-stage (quant_in): per-token activation quantization of X
    # BEFORE the launch — plain jnp ops, so quantize -> GEMM -> dequant is
    # still ONE kernel launch; the produced row scales ride the extras
    # stream into the fused dequant tail.
    ep_def = get_epilogue(epilogue.kind)
    pre_quant = ep_def.pre is not None
    if pre_quant:
        if bias is not None:
            raise ValueError(
                f"epilogue {epilogue.kind!r} does not take a bias (the "
                "fused per-row dequant would rescale it)")
        x, pre_extras = ep_def.pre(epilogue, x)
        extras = tuple(pre_extras) + tuple(extras)

    def _kernel(a, b, wp, scale, ws=None):
        op = b if b is not None else wp if wp is not None else ws
        return mpgemm_pallas_spec(
            a, op, bias=bias, scale=scale,
            extras=extras, spec=spec, epilogue=epilogue,
            out_dtype=out_dtype, interpret=interp)

    if is_sparse(w):
        # Tile-sparse B: kernel backends walk only the stored tiles (the
        # sparse launch path); the policy logic mirrors the packed branch
        # — the payload IS the weight-side storage, so only the x side
        # ever needs a per-call cast/quantize.
        layout = w.layout
        if kernel_backend and (pre_quant or not (policy.quantized
                                                 and layout.dtype != "int8")):
            if pre_quant:
                # X is already row-quantized int8; an int8 payload dots in
                # int32 against it, a float payload upcasts in-kernel.
                if layout.dtype == "int8":
                    return _kernel(x, None, None, None, w)
                w = w.astype(policy.compute_dtype)
                return _kernel(x.astype(jnp.dtype(policy.compute_dtype)),
                               None, None, None, w)
            if policy.quantized:
                xq, sx = quantize_per_tensor(x)
                return _kernel(xq, None, None, sx, w)
            xc = x.astype(jnp.dtype(policy.compute_dtype))
            if layout.dtype != "int8":
                w = w.astype(policy.compute_dtype)
            return _kernel(xc, None, None, None, w)
        # XLA fallback — or a float payload under the dynamic-int8 policy:
        # densify (zeros at pruned tiles) and reuse the dense-path logic.
        from repro.sparse.sparsify import densify_operand
        w = densify_operand(w)
        spec = dataclasses.replace(spec, sparse=False, tile_scaled=False,
                                   trans_b=False)

    if is_packed(w):
        layout = w.layout
        native = kernel_backend and layout.kernel_native
        if native and (pre_quant or layout.per_tile_scales
                       or not policy.quantized):
            if pre_quant:
                # X is already row-quantized int8.  Quantized payloads
                # (int8/int4/fp8) dequant via their per-tile scales riding
                # the accumulation; float payloads upcast the int X values
                # in-kernel (the row scale still dequantizes in the tail).
                if layout.per_tile_scales:
                    return _kernel(x, None, w, None)
                w = w.astype(policy.compute_dtype)
                return _kernel(x.astype(jnp.dtype(policy.compute_dtype)),
                               None, w, None)
            if policy.quantized:
                if layout.codec is not None and not layout.codec.integer:
                    # fp8 payload under the dynamic-int8 policy: there is
                    # no int8 x fp8 dot — stream bf16 activations against
                    # the fp8 tiles (per-tile scales still dequant).
                    return _kernel(x.astype(jnp.bfloat16), None, w, None)
                # Dynamic x-side quantization only: the weight side is
                # already int-valued with per-tile scales in the payload.
                xq, sx = quantize_per_tensor(x)
                return _kernel(xq, None, w, sx)
            xc = x.astype(jnp.dtype(policy.compute_dtype))
            if not layout.per_tile_scales:
                w = w.astype(policy.compute_dtype)  # no-op when packed right
            return _kernel(xc, None, w, None)
        # XLA fallback — a float payload under the dynamic-int8 policy
        # (whose per-tensor weight quantization needs a dense array), a
        # bit-emulated codec the kernel can't decode, or a non-kernel
        # backend: unpack once and reuse the dense-path logic.
        from repro.packing.pack import unpack_operand
        w = unpack_operand(w, backend=backend if native else None)
        spec = dataclasses.replace(spec, packed=False, tile_scaled=False,
                                   trans_b=False)

    if not kernel_backend:
        _note_xla_dispatch(x, w, spec, epilogue, ep_def, out_dtype)

    if pre_quant:
        # Dense weights under activation quantization: per-tensor quantize
        # the weight side so the dot runs int8 x int8 -> int32; the weight
        # scale rides the scalar dequant slot, the row scales the tail.
        wq, sw = quantize_per_tensor(w)
        if kernel_backend:
            return _kernel(x, wq, None, sw)
        acc = jax.lax.dot_general(x, wq, _dims(spec),
                                  preferred_element_type=jnp.int32)
        return _xla_epilogue(epilogue, acc, bias, sw, extras,
                             grouped).astype(out_dtype)

    if policy.quantized:
        xq, sx = quantize_per_tensor(x)
        wq, sw = quantize_per_tensor(w)
        scale = sx * sw
        if kernel_backend:
            return _kernel(xq, wq, None, scale)
        acc = jax.lax.dot_general(xq, wq, _dims(spec),
                                  preferred_element_type=jnp.int32)
        return _xla_epilogue(epilogue, acc, bias, scale, extras,
                             grouped).astype(out_dtype)

    cd = jnp.dtype(policy.compute_dtype)
    xc = x.astype(cd)
    wc = w.astype(cd)
    if wc.dtype != w.dtype:
        wc = jax.lax.optimization_barrier(wc)  # see docstring
    if kernel_backend:
        return _kernel(xc, wc, None, None)
    acc = jax.lax.dot_general(
        xc, wc, _dims(spec),
        preferred_element_type=jnp.dtype(acc_dtype or policy.acc_dtype),
    )
    return _xla_epilogue(epilogue, acc, bias, None, extras,
                         grouped).astype(out_dtype)


def _bwd_flavor(policy: PrecisionPolicy):
    """(backward policy, backward partial-sum dtype) — see :func:`_gemm_bwd`."""
    bwd_policy = get_policy("fp32" if policy.name == "fp32" else "bf16")
    bwd_acc = "float32" if policy.name == "fp32" else "bfloat16"
    return bwd_policy, bwd_acc


def _packed_weight_cotangent(wp: PackedOperand, dw_dense) -> PackedOperand:
    """Cotangent pytree for a packed-weight primal.

    Float payloads: pack/unpack is a LINEAR bijection onto the tile grid
    (zero pads aside), so the payload cotangent is simply the packed dense
    gradient — packed weights stay trainable.  int8 payloads (per-tile
    quantized) have no usable tangent space: integer leaves get float0
    zeros (JAX's unit cotangent for int primals), scales zeros — the
    weight is frozen, the standard serving configuration.
    """
    from repro.packing.pack import pack_reference
    layout = wp.layout
    if layout.per_tile_scales:
        return PackedOperand(
            np.zeros(wp.payload.shape, jax.dtypes.float0),
            jnp.zeros_like(wp.scales), layout)
    # dw_dense is in the LOGICAL (k, n) orientation (the bwd GEMMs resolve
    # the transpose), so the cotangent pack must not re-apply the layout's
    # recorded source transpose.
    payload_ct, _ = pack_reference(
        dw_dense, dataclasses.replace(layout, trans_w=False))
    return PackedOperand(payload_ct, None, layout)


def _sparse_weight_cotangent(ws: TileSparseOperand,
                             dw_dense) -> TileSparseOperand:
    """Cotangent pytree for a tile-sparse weight primal.

    The defining property of the sparse op's VJP: the dense gradient is
    MASKED to the stored tiles — pruned tiles are structural zeros with no
    tangent space, so training under a fixed pattern can never resurrect
    them (and the trailing anchor zero tile stays a constant: zero
    cotangent).  int8 payloads are frozen via float0, exactly as packed.
    """
    from repro.sparse.sparsify import payload_cotangent
    layout = ws.layout
    if layout.per_tile_scales:
        return TileSparseOperand(
            np.zeros(ws.payload.shape, jax.dtypes.float0),
            jnp.zeros_like(ws.scales), layout)
    return TileSparseOperand(payload_cotangent(dw_dense, layout), None,
                             layout)


# --- the one differentiable core ---------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gemm_core(x, w, bias, extras, spec: GemmSpec, epilogue: EpilogueSpec,
               policy_name: str, backend: str):
    """THE custom-VJP core: every mp_dot / mp_dot_grouped call lands here.

    ``spec``/``epilogue`` are static (hashable) and carry the full dispatch
    decision; ``w`` is a dense array or a :class:`PackedOperand` pytree
    (never a {"q","scale"} dict — the wrappers dequantize those first so
    dict primals never need dict cotangents); ``extras`` is the tuple of
    epilogue fusion operands in registry order.
    """
    return _apply_gemm(x, w, bias, extras, spec, epilogue,
                       get_policy(policy_name), backend)


def _gemm_fwd(x, w, bias, extras, spec, epilogue, policy_name, backend):
    y = _gemm_core(x, w, bias, extras, spec, epilogue, policy_name, backend)
    return y, (x, w, bias, extras)


def _gemm_bwd(spec: GemmSpec, epilogue: EpilogueSpec, policy_name, backend,
              res, dy):
    """One backward rule for every spec: fused-transpose GEMMs + registry
    epilogue backward.

    Non-quantized sibling precision (STE for int8), bf16 partial sums so
    TP/FSDP/EP gradient reductions move bf16 on the wire (see
    :func:`_bwd_flavor`).  Packed weights: the payload's layout serves the
    FORWARD read pattern; backward contracts over N, for which the dense
    on-the-fly-transpose kernel path already exists — so the weight is
    unpacked once and the gradient re-packed (int8 payloads stay frozen via
    float0).  Fused epilogues recompute the pre-tail value z only when the
    registry entry's backward needs it (one extra GEMM — standard
    rematerialization; the fused forward never materializes z).
    """
    x, w, bias, extras = res
    policy = get_policy(policy_name)
    bwd_policy, bwd_acc = _bwd_flavor(policy)
    grouped = spec.grouped

    packed = is_packed(w)
    sparse = is_sparse(w)
    if packed:
        from repro.packing.pack import unpack_operand
        kb = backend if backend in ("pallas", "interpret") else None
        w_dense = unpack_operand(w, backend=kb)  # (k,n)/(g,k,n), trans resolved
        w_trans = False
    elif sparse:
        # Densify once (zeros at pruned tiles): backward contracts over N,
        # for which the dense on-the-fly-transpose path exists; the weight
        # cotangent is then masked back to the stored tiles.
        from repro.sparse.sparsify import densify_operand
        w_dense = densify_operand(w)
        w_trans = False
    else:
        w_dense = w
        w_trans = spec.trans_b

    z = None
    if epilogue_needs_pre(epilogue):
        zspec = dataclasses.replace(
            spec, packed=False, sparse=False, tile_scaled=False,
            trans_b=w_trans, ragged=False, out_dtype="float32")
        z = _apply_gemm(x, w_dense, bias, (), zspec,
                        EpilogueSpec(alpha=epilogue.alpha), bwd_policy,
                        backend)
    dz, dextras = epilogue_bwd(epilogue, z, extras, dy.astype(jnp.float32))

    # Chain through the epilogue's alpha pre-scale (z = alpha·acc + bias, so
    # dacc = alpha·dz); bias adds AFTER alpha, so dbias below stays unscaled.
    # The dynamic-int8 dequant scale is deliberately NOT chained (STE: the
    # backward runs in the non-quantized sibling policy).
    dzg = dz * jnp.asarray(epilogue.alpha, dz.dtype) \
        if epilogue.alpha != 1.0 else dz

    # dx = dzg @ op(w)^T : if w stored (k,n) -> dzg(m,n) x w(k,n)^T == trans_b=True
    #                      if w stored (n,k) (trans_w) -> plain dzg @ w.
    dx_spec = dataclasses.replace(
        spec, packed=False, sparse=False, tile_scaled=False, trans_a=False,
        trans_b=not w_trans, ragged=False, out_dtype=str(x.dtype))
    dx = _apply_gemm(dzg, w_dense, None, (), dx_spec, _LINEAR, bwd_policy,
                     backend, acc_dtype=bwd_acc)

    # dw: (k,n) = x^T @ dzg ; transposed storage: (n,k) = dzg^T @ x.
    if (packed or sparse) and w.layout.per_tile_scales:
        dw_dense = None  # int8 payload: no tangent space, frozen weight
    else:
        dw_spec = dataclasses.replace(
            spec, packed=False, sparse=False, tile_scaled=False,
            trans_a=True, trans_b=False, ragged=False,
            out_dtype=str(w_dense.dtype))
        dw_dense = (_apply_gemm(dzg, x, None, (), dw_spec, _LINEAR,
                                bwd_policy, backend, acc_dtype=bwd_acc)
                    if w_trans else
                    _apply_gemm(x, dzg, None, (), dw_spec, _LINEAR,
                                bwd_policy, backend, acc_dtype=bwd_acc))
    dw = (_packed_weight_cotangent(w, dw_dense) if packed
          else _sparse_weight_cotangent(w, dw_dense) if sparse
          else dw_dense)

    # f32 accumulation for the reduction, cast back to the primal's dtype
    # (custom-VJP cotangents must match primal dtypes).
    dbias = None
    if bias is not None:
        dbias = jnp.sum(dz, axis=1 if grouped else 0,
                        dtype=jnp.float32).astype(bias.dtype)
    return dx, dw, dbias, dextras


_gemm_core.defvjp(_gemm_fwd, _gemm_bwd)


# --- op-level spec assembly ---------------------------------------------------

def _build_epilogue(epilogue, activation, gate, residual, epilogue_operands,
                    quant_in=False):
    """Resolve the op-level EpilogueSpec + ordered extras tuple.

    Convenience kwargs (``activation``/``gate``/``residual``) infer the
    registry kind; an explicit ``epilogue`` spec wins, with
    ``epilogue_operands`` naming any custom entry's streamed operands.
    ``quant_in=True`` selects the activation-quantization family (explicit
    opt-in — pre-stage kinds are never inferred from operands).  The
    shared registry-driven resolution lives in core/gemm_spec.py.
    """
    named = {"gate": gate, "residual": residual}
    if epilogue_operands:
        named.update(epilogue_operands)
    if quant_in:
        if epilogue is not None:
            raise ValueError(
                "pass quant_in=True OR an explicit epilogue spec, not both")
        if gate is not None:
            raise ValueError(
                "quant_in does not compose with the gated epilogue")
        kind = "quant_in_residual" if residual is not None else "quant_in"
        epilogue = EpilogueSpec(kind=kind, activation=activation)
        activation = None
    epilogue, extras = resolve_epilogue(named, epilogue=epilogue,
                                        activation=activation)
    if epilogue.beta != 0.0:
        raise ValueError(
            "beta·C accumulation is a kernel-level epilogue "
            "(mpgemm_pallas); mp_dot has no C operand")
    return epilogue, extras


def _dequant_static(w, policy):
    """Dequantize a static-int8 {"q","scale"} dict BEFORE the custom-VJP
    core: the bwd rule contracts against w and must see an array primal (a
    dict residual has no dtype and no array cotangent).  XLA still fuses
    the dequant into the GEMM read; differentiation flows through the
    dequant natively."""
    from repro.core.quantization import dequantize_tensor, is_quantized
    if not is_quantized(w):
        return w
    return dequantize_tensor(
        w, jnp.float32 if policy.quantized
        else jnp.dtype(policy.compute_dtype))


def _resolve_operand(name, b, w, b_sparse):
    """Collapse the legacy ``w=``/``b_sparse=`` keywords into the
    polymorphic ``b`` operand (dense array / PackedOperand /
    TileSparseOperand — dispatch is by type).  The keywords survive only
    as DeprecationWarning shims."""
    if sum(x is not None for x in (b, w, b_sparse)) != 1:
        raise ValueError(f"{name}: exactly one of b / w / b_sparse "
                         "is required")
    if w is not None:
        obs.warn_deprecated(
            f"{name}.w",
            f"{name}(w=...) is deprecated; pass the operand positionally "
            "as `b`", stacklevel=3)
        return w
    if b_sparse is not None:
        obs.warn_deprecated(
            f"{name}.b_sparse",
            f"{name}(b_sparse=...) is deprecated; pass the operand as the "
            "polymorphic `b` argument (dispatch is by operand type)",
            stacklevel=3)
        return b_sparse
    return b


def mp_dot(
    x: jax.Array,
    b: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    *,
    w: Optional[jax.Array] = None,
    b_sparse: Optional[TileSparseOperand] = None,
    policy="bf16",
    trans_w: bool = False,
    backend: Optional[str] = None,
    out_dtype=None,
    activation: Optional[str] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    epilogue: Optional[EpilogueSpec] = None,
    epilogue_operands: Optional[dict] = None,
    quant_in: bool = False,
) -> jax.Array:
    """y[..., n] = tail(x[..., k] @ (b[n, k]ᵀ if trans_w else b[k, n]) + bias).

    ``tail`` is the registry epilogue: ``activation`` alone fuses an
    activation into the GEMM's store; ``gate`` fuses ``act(·) · gate`` (the
    SwiGLU/GeGLU gating step — one kernel launch instead of a GEMM plus an
    elementwise pass); ``residual`` fuses ``act(·) + residual``.  Both take
    an operand shaped like the output.  All fusions differentiate through
    the registry's backward rules.

    ``trans_w=True`` is the on-the-fly-transposition path — used e.g. for
    tied-embedding logits (weights stored (vocab, d_model)).

    ``b`` is POLYMORPHIC — dispatch is by operand type, not by keyword:

    * a dense array — the plain mixed-precision GEMM;
    * a :class:`repro.packing.PackedOperand` (pre-packed at parameter-load
      time): the forward then reads the tiled payload directly — no
      per-call cast/dequant/transposition — and ``trans_w`` must match the
      orientation recorded at pack time (the transpose is already resolved
      inside the payload);
    * a :class:`repro.sparse.TileSparseOperand`: the forward then visits
      ONLY the stored tiles (grid = stored-tile schedule, scalar-prefetched
      index maps), the custom VJP masks the weight cotangent to the stored
      tiles (pruned tiles have no tangent space — a fixed pattern can never
      be resurrected by training), and ``dx`` contracts against the
      densified weight.  Composes with every registry epilogue and
      precision policy; int8 payloads are frozen via float0 like packed
      int8.

    ``quant_in=True`` turns on per-token activation quantization: a
    registry pre-stage computes per-row amax scales for ``x``, the GEMM
    runs int8 (against per-tile-quantized packed payloads or a per-tensor-
    quantized dense weight), and the per-row dequant (+activation
    [+residual]) is fused into the epilogue — quantize -> GEMM -> dequant
    in ONE kernel launch.  The backward is straight-through (gradients of
    the float GEMM, ignoring the rounding).  Excludes ``bias``/``gate``.

    ``w=`` and ``b_sparse=`` are deprecated keyword aliases for ``b``.
    """
    w = _resolve_operand("mp_dot", b, w, b_sparse)
    policy = get_policy(policy)
    backend = backend or cfg.get_gemm_backend()
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    if bias is not None:
        bias = bias.reshape(-1)
    epilogue, extras = _build_epilogue(epilogue, activation, gate, residual,
                                       epilogue_operands, quant_in=quant_in)
    extras = tuple(e.reshape(-1, e.shape[-1]) for e in extras)
    out_s = str(jnp.dtype(out_dtype)) if out_dtype is not None else None
    if is_packed(w) or is_sparse(w):
        kind = "PackedOperand" if is_packed(w) else "TileSparseOperand"
        if w.layout.g != 1:
            raise ValueError(f"grouped {kind}: use mp_dot_grouped")
        if trans_w != w.layout.trans_w:
            raise ValueError(
                f"trans_w={trans_w} but the operand was packed with "
                f"trans_w={w.layout.trans_w} (transposition is resolved at "
                f"pack time)")
        n = w.layout.n
        spec = GemmSpec(packed=is_packed(w), sparse=is_sparse(w),
                        tile_scaled=w.layout.per_tile_scales,
                        out_dtype=out_s)
    else:
        w = _dequant_static(w, policy)
        n = w.shape[0] if trans_w else w.shape[-1]
        spec = GemmSpec(trans_b=trans_w, out_dtype=out_s)
    y2d = _gemm_core(x2d, w, bias, extras, spec, epilogue, policy.name,
                     backend)
    return y2d.reshape(*lead, n)


# --- grouped / batched op ----------------------------------------------------

def mp_dot_grouped(
    x: jax.Array,
    b: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    *,
    w: Optional[jax.Array] = None,
    b_sparse: Optional[TileSparseOperand] = None,
    policy="bf16",
    trans_w: bool = False,
    backend: Optional[str] = None,
    group_sizes: Optional[jax.Array] = None,
    out_dtype=None,
    activation: Optional[str] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    epilogue: Optional[EpilogueSpec] = None,
    epilogue_operands: Optional[dict] = None,
    quant_in: bool = False,
) -> jax.Array:
    """y[g, m, n] = tail(x[g, m, k] @ (b[g, n, k]ᵀ if trans_w else b[g, k, n]) + bias[g, n]).

    The grouped sibling of :func:`mp_dot`: G independent GEMMs — MoE expert
    blocks, batched projections — in ONE kernel launch with the group as the
    leading grid axis, under the same precision policies, plan cache (keyed
    with the extra ``g`` dimension and the epilogue tag), fused-transpose
    custom VJP, and registry epilogues (``gate``/``residual`` are (G, M, N)
    operands — e.g. the fused MoE SwiGLU gating).

    ``group_sizes`` (shape (G,), int) marks ragged groups: rows ``>=
    group_sizes[g]`` of each output group are forced to zero, so capacity-
    padded expert buffers contribute neither output nor (via the masked
    cotangent) gradient.  The mask sits outside the custom VJP, so autodiff
    handles it natively.

    ``out_dtype`` overrides the policy's output dtype — MoE keeps f32
    activations between the expert GEMMs and the combine, matching the
    accumulator precision.

    ``b`` is polymorphic like :func:`mp_dot`'s: a dense (G, K, N) array, a
    grouped :class:`repro.packing.PackedOperand`, or a grouped
    :class:`repro.sparse.TileSparseOperand` — the sparse form walks only
    the union of every group's stored tiles — per-expert tile pruning
    shrinks the launch grid itself — with the same masked-cotangent VJP as
    :func:`mp_dot`.  ``w=`` and ``b_sparse=`` are deprecated keyword
    aliases for ``b``.
    """
    if x.ndim != 3:
        raise ValueError(f"mp_dot_grouped expects x of rank 3, got {x.shape}")
    w = _resolve_operand("mp_dot_grouped", b, w, b_sparse)
    policy = get_policy(policy)
    backend = backend or cfg.get_gemm_backend()
    epilogue, extras = _build_epilogue(epilogue, activation, gate, residual,
                                       epilogue_operands, quant_in=quant_in)
    out_s = str(jnp.dtype(out_dtype)) if out_dtype is not None else None
    if is_packed(w) or is_sparse(w):
        if w.layout.g != x.shape[0]:
            raise ValueError(
                f"group mismatch: x has {x.shape[0]}, payload {w.layout.g}")
        if trans_w != w.layout.trans_w:
            raise ValueError(
                f"trans_w={trans_w} but the operand was packed with "
                f"trans_w={w.layout.trans_w}")
        spec = GemmSpec(grouped=True, packed=is_packed(w),
                        sparse=is_sparse(w),
                        tile_scaled=w.layout.per_tile_scales,
                        ragged=group_sizes is not None, out_dtype=out_s)
    else:
        w = _dequant_static(w, policy)
        spec = GemmSpec(grouped=True, trans_b=trans_w,
                        ragged=group_sizes is not None, out_dtype=out_s)
    if bias is not None and bias.ndim == 1:
        # Normalize a shared (N,) bias to (G, N) BEFORE the custom-VJP core:
        # outside it autodiff sum-reduces the (G, N) bias cotangent back to
        # (N,); inside, backends would disagree on broadcasting.
        bias = jnp.broadcast_to(bias[None, :], (x.shape[0], bias.shape[0]))
    y = _gemm_core(x, w, bias, extras, spec, epilogue, policy.name, backend)
    if group_sizes is not None:
        sizes = jnp.asarray(group_sizes, jnp.int32).reshape(-1, 1, 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        y = jnp.where(rows < sizes, y, jnp.zeros_like(y))
    return y


def _as_grouped_matmul(spec: str, n_ops: int) -> Optional[bool]:
    """Is ``spec`` a grouped matmul ``Xab,Xbc->Xac`` (any letters)?

    Returns ``trans_w`` (False for ``Xab,Xbc->Xac``, True for
    ``Xab,Xcb->Xac``) or None when the spec is not a grouped matmul.
    """
    if n_ops != 2:
        return None
    try:
        ins, out = spec.replace(" ", "").split("->")
        a, b = ins.split(",")
    except ValueError:
        return None
    if not (len(a) == len(b) == len(out) == 3 and len(set(a)) == 3):
        return None
    if not (a[0] == b[0] == out[0] and out[1] == a[1]):
        return None
    if b[1] == a[2] and out[2] == b[2] and len({a[0], a[1], a[2], b[2]}) == 4:
        return False           # Xab,Xbc->Xac
    if b[2] == a[2] and out[2] == b[1] and len({a[0], a[1], a[2], b[1]}) == 4:
        return True            # Xab,Xcb->Xac (stored-transposed rhs)
    return None


def mp_einsum(spec: str, *operands, policy="bf16") -> jax.Array:
    """Policy-aware einsum for non-2D contractions (attention score/value).

    Grouped-matmul specs (``gmk,gkn->gmn`` and the stored-transposed
    ``gmk,gnk->gmn``, any letters) are routed through :func:`mp_dot_grouped`
    — i.e. through the spec-driven MPGEMM core and plan cache — rather than
    a raw einsum.  Anything else runs on XLA with the policy's
    compute/accumulate dtypes; quantized policies fall back to their bf16
    sibling there (per-slice dynamic quantization needs the grouped path).
    """
    trans_w = _as_grouped_matmul(spec, len(operands))
    if trans_w is not None and all(
        jnp.dtype(o.dtype).kind == "f" for o in operands
    ):
        return mp_dot_grouped(operands[0], operands[1], policy=policy,
                              trans_w=trans_w)
    policy = get_policy(policy)
    if policy.quantized:
        policy = get_policy("bf16")
    cd = jnp.dtype(policy.compute_dtype)
    ops = [o.astype(cd) if jnp.dtype(o.dtype).kind == "f" else o for o in operands]
    out = jnp.einsum(
        spec, *ops, preferred_element_type=jnp.dtype(policy.acc_dtype)
    )
    return out.astype(policy.out_dtype)
