"""Sharded MPGEMM: tensor/expert-parallel ``mp_dot`` with overlap.

The paper's core move is hierarchical cache-aware partitioning; a device
mesh is the next level of that hierarchy.  This module teaches the whole
spec-driven GEMM stack (``core/gemm.py`` → ``kernels/mpgemm.py``) to run
under ``shard_map`` over the 1-D tensor-parallel meshes of
``launch/mesh.py::make_tp_mesh``:

``mp_dot_sharded`` — one logical ``y = x @ b`` with B (and optionally X)
partitioned over a mesh axis.  Three partitions:

  * ``"column"`` — B split along N, X replicated.  No collective; the
    output comes back N-sharded.  The only partition that supports the
    polymorphic packed/tile-sparse B operands (see below).
  * ``"row"``    — B and X split along K.  Each device holds one K-slice
    partial of the FULL (M, N) product, so a reduction over the axis is
    required.  ``overlap="ring"`` (default) runs a **ring reduce-scatter
    matmul**: the local K-contribution is computed one N-chunk at a time,
    and between chunk GEMMs the partial accumulator takes one ``ppermute``
    hop around the ring — P-1 collective steps interleaved with P tile-
    compute steps (the traced jaxpr literally alternates ``dot``/
    ``ppermute``; ``benchmarks/bench_distributed.py`` gates on it), instead
    of ``overlap="blocking"``'s single monolithic ``psum`` after all
    compute.
  * ``"gather"`` — X split along M (sequence parallel), B split along N.
    ``overlap="ring"`` runs a **ring all-gather matmul**: each step
    multiplies the M-shard currently held against the local N-shard and
    writes its output rows, then passes the shard one hop on;
    ``overlap="blocking"`` all-gathers X first, then runs one local GEMM.

``mp_dot_grouped_sharded`` — grouped (MoE expert) GEMMs, expert-parallel:
experts are split over the mesh axis and tokens travel.  Inside the
``shard_map`` an ``all_to_all`` re-shards X from token-sharded
``(G, M/P, K)`` to expert-sharded ``(G/P, M, K)``, the local grouped
MPGEMM runs over the device's experts only, and a second ``all_to_all``
restores token sharding — the classic MoE dispatch/combine pair with the
weights never moving.

**Per-shard planning.**  Inside ``shard_map`` every shape IS the local
shard, so the block planner / plan-cache lookups the kernel launch makes
at trace time (``mpgemm_pallas_spec``) automatically compute CMR on the
per-device (M, N, K) — the mesh is one more level of the paper's
partitioning hierarchy.  Each sharded trace additionally runs under
``tuning.plan_cache.mesh_namespace(mesh_plan_tag(...))``, so tuned sharded
plans live in a ``|mesh=tp4[model]``-suffixed key namespace and never
alias single-device tunings of the same local shape.

**Polymorphic B operands.**  ``shard_operand`` splits a dense array,
:class:`~repro.packing.PackedOperand`, or
:class:`~repro.sparse.TileSparseOperand` along its N-tile axis (grouped
operands: along G) into per-shard operands whose payloads carry only that
shard's tiles.  Packed/sparse shards cannot ride a single ``shard_map``
program — their static layout aux (tile counts, sparse nnz/schedule)
differs per shard, and SPMD requires one program — so
``mp_dot_sharded`` runs them as per-shard programs concatenated under an
output sharding constraint: under ``jit`` over the mesh, GSPMD places each
shard's compute (and therefore its payload) on its own device group.  The
dense paths carry the overlap machinery and the jaxpr gate.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.gemm import mp_dot, mp_dot_grouped
from repro.packing.layout import PackedOperand, is_packed
from repro.sparse.layout import TileSparseOperand, is_sparse
from repro.tuning.plan_cache import mesh_namespace

PARTITIONS = ("column", "row", "gather")
OVERLAPS = ("ring", "blocking")

Operand = Union[jax.Array, PackedOperand, TileSparseOperand]


# --------------------------------------------------------------------- mesh

def mesh_axis_size(mesh, axis: str) -> int:
    return int(dict(mesh.shape)[axis])


def mesh_plan_tag(mesh, axis: str) -> str:
    """Plan-cache namespace tag for a sharded GEMM over ``mesh[axis]``.

    Keyed by axis SIZE (not device identity): a tuned per-shard plan is
    valid for any 4-way slice of any mesh, exactly like single-device plans
    are valid for any device of the same hardware generation.
    """
    return f"tp{mesh_axis_size(mesh, axis)}[{axis}]"


def _check_div(what: str, value: int, shards: int) -> int:
    if value % shards != 0:
        raise ValueError(
            f"{what} = {value} is not divisible by the mesh axis size "
            f"{shards}; pad the operand or pick a different partition")
    return value // shards


# --------------------------------------------------- operand sharding (N/G)

def _shard_dense(b: jax.Array, shards: int, *, axis: str,
                 trans_w: bool) -> Tuple[jax.Array, ...]:
    if axis == "g":
        if b.ndim != 3:
            raise ValueError(f"group sharding needs a (G, K, N) operand, "
                             f"got shape {b.shape}")
        _check_div("G", b.shape[0], shards)
        return tuple(jnp.split(b, shards, axis=0))
    n_axis = (b.ndim - 2) if trans_w else (b.ndim - 1)
    _check_div("N", b.shape[n_axis], shards)
    return tuple(jnp.split(b, shards, axis=n_axis))


def _shard_packed(p: PackedOperand, shards: int, *,
                  axis: str) -> Tuple[PackedOperand, ...]:
    lay = p.layout
    grouped = lay.g != 1
    if axis == "g":
        if not grouped:
            raise ValueError("group sharding needs a grouped PackedOperand")
        gl = _check_div("G", lay.g, shards)
        parts = []
        for s in range(shards):
            payload = p.payload[s * gl:(s + 1) * gl]
            scales = (p.scales[s * gl:(s + 1) * gl]
                      if p.scales is not None else None)
            if gl == 1:  # PackedLayout g=1 means "not grouped": drop the axis
                payload = payload[0]
                scales = scales[0] if scales is not None else None
            parts.append(PackedOperand(
                payload, scales, dataclasses.replace(lay, g=gl)))
        return tuple(parts)
    # N sharding: the shard boundary must fall on the bn tile lattice, so
    # each shard owns whole (bk, bn) tiles and no padding column splits.
    nl = _check_div("N", lay.n, shards)
    if nl % lay.bn != 0:
        raise ValueError(
            f"per-shard N = {nl} is not a multiple of the packed tile width "
            f"bn = {lay.bn}; shard boundaries must fall on tile boundaries")
    nnb_l = nl // lay.bn
    j_axis = 2 if grouped else 1
    parts = []
    for s in range(shards):
        sl = [slice(None)] * p.payload.ndim
        sl[j_axis] = slice(s * nnb_l, (s + 1) * nnb_l)
        payload = p.payload[tuple(sl)]
        scales = None
        if p.scales is not None:
            ssl = [slice(None)] * p.scales.ndim
            ssl[j_axis] = slice(s * nnb_l, (s + 1) * nnb_l)
            scales = p.scales[tuple(ssl)]
        parts.append(PackedOperand(
            payload, scales, dataclasses.replace(lay, n=nl)))
    return tuple(parts)


def _sparse_column_slice(p: TileSparseOperand, cols: Sequence[int],
                         *, n: int, g: int) -> TileSparseOperand:
    """Rebuild a TileSparseOperand keeping only BSR columns ``cols``
    (which must be contiguous in the column-major (g, j) order)."""
    lay = p.layout
    lo, hi = lay.indptr[cols[0]], lay.indptr[cols[-1] + 1]
    indptr = tuple(lay.indptr[c] - lo for c in cols)
    indptr = indptr + (hi - lo,)
    indices = lay.indices[lo:hi]
    # Stored tiles of contiguous columns are a contiguous payload slice;
    # re-append the shared trailing zero tile (slot nnz) for anchor visits.
    payload = jnp.concatenate([p.payload[lo:hi], p.payload[lay.nnz:]], axis=0)
    scales = None
    if p.scales is not None:
        scales = jnp.concatenate([p.scales[lo:hi], p.scales[lay.nnz:]],
                                 axis=0)
    new_lay = dataclasses.replace(lay, n=n, g=g, indptr=indptr,
                                  indices=indices)
    return TileSparseOperand(payload, scales, new_lay)


def _shard_sparse(p: TileSparseOperand, shards: int, *,
                  axis: str) -> Tuple[TileSparseOperand, ...]:
    lay = p.layout
    if axis == "g":
        if lay.g == 1:
            raise ValueError("group sharding needs a grouped "
                             "TileSparseOperand")
        gl = _check_div("G", lay.g, shards)
        return tuple(
            _sparse_column_slice(
                p, range(s * gl * lay.nnb, (s + 1) * gl * lay.nnb),
                n=lay.n, g=gl)
            for s in range(shards))
    nl = _check_div("N", lay.n, shards)
    if nl % lay.bn != 0:
        raise ValueError(
            f"per-shard N = {nl} is not a multiple of the sparse tile width "
            f"bn = {lay.bn}; shard boundaries must fall on tile boundaries")
    nnb_l = nl // lay.bn
    parts = []
    for s in range(shards):
        cols = [gi * lay.nnb + j
                for gi in range(lay.g)
                for j in range(s * nnb_l, (s + 1) * nnb_l)]
        if lay.g > 1:
            # Column-major (g, j) order: an N slice of a grouped operand is
            # NOT contiguous across groups, so rebuild per group and re-fold.
            raise ValueError(
                "N-sharding a grouped sparse operand is unsupported; shard "
                "grouped operands along G (expert parallelism)")
        parts.append(_sparse_column_slice(p, cols, n=nl, g=lay.g))
    return tuple(parts)


def shard_operand(b: Operand, shards: int, *, axis: str = "n",
                  trans_w: bool = False) -> Tuple[Operand, ...]:
    """Split a GEMM B operand into ``shards`` per-device operands.

    ``axis="n"`` splits output columns on the tile lattice (tensor
    parallelism); ``axis="g"`` splits expert groups (expert parallelism).
    Packed and tile-sparse operands keep only their shard's payload tiles —
    the per-device memory story: a 4-way shard holds 1/4 of the payload
    bytes (plus the sparse zero-anchor tile).
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if axis not in ("n", "g"):
        raise ValueError(f"axis must be 'n' or 'g', got {axis!r}")
    if shards == 1:
        return (b,)
    if is_packed(b):
        return _shard_packed(b, shards, axis=axis)
    if is_sparse(b):
        return _shard_sparse(b, shards, axis=axis)
    return _shard_dense(b, shards, axis=axis, trans_w=trans_w)


# ------------------------------------------------------- dense shard bodies

def _ring_row_body(axis: str, size: int, dot):
    """Ring reduce-scatter matmul body: P chunk GEMMs, P-1 ppermute hops.

    Device ``me`` computes its local-K contribution to N-chunk
    ``(me - t - 1) mod P`` at step t and adds the accumulator received from
    its ring predecessor; after P-1 hops the accumulator arriving at device
    ``me`` has visited every device exactly when it carries chunk ``me`` —
    the fully reduced shard the out_spec reassembles.  The python loop
    unrolls, so the traced program literally interleaves one ``ppermute``
    between consecutive chunk GEMMs — that is the overlap the XLA/TPU
    scheduler exploits (and the jaxpr gate asserts).
    """
    perm = [(i, (i + 1) % size) for i in range(size)]

    def body(xl, bl):
        nc = bl.shape[-1] // size
        me = jax.lax.axis_index(axis)

        def chunk(i):
            start = jnp.mod(i, size) * nc
            return jax.lax.dynamic_slice_in_dim(bl, start, nc, axis=1)

        acc = dot(xl, chunk(me - 1))
        for t in range(1, size):
            recv = jax.lax.ppermute(acc, axis, perm)
            acc = recv + dot(xl, chunk(me - t - 1))
        return acc

    return body


def _ring_gather_body(axis: str, size: int, dot):
    """Ring all-gather matmul body: each step multiplies the currently held
    M-shard of X against the local N-shard of B and writes its output rows,
    then forwards the shard one ring hop — compute on shard t overlaps the
    transfer of shard t+1 (double buffering in XLA's async scheduler)."""
    perm = [(i, (i + 1) % size) for i in range(size)]

    def body(xl, bl):
        ml = xl.shape[0]
        me = jax.lax.axis_index(axis)
        buf = xl
        out = None
        for t in range(size):
            part = dot(buf, bl)
            y = jnp.zeros((ml * size, part.shape[1]), part.dtype) \
                if out is None else out
            src = jnp.mod(me - t, size)
            out = jax.lax.dynamic_update_slice_in_dim(y, part, src * ml,
                                                      axis=0)
            if t < size - 1:
                buf = jax.lax.ppermute(buf, axis, perm)
        return out

    return body


# ---------------------------------------------------------------- mp_dot

def mp_dot_sharded(
    x: jax.Array,
    b: Operand,
    bias: Optional[jax.Array] = None,
    *,
    mesh,
    axis: str = "model",
    partition: str = "column",
    overlap: str = "ring",
    policy="bf16",
    backend: Optional[str] = None,
    out_dtype=None,
) -> jax.Array:
    """``mp_dot`` partitioned over ``mesh[axis]``; returns the global (M, N).

    See the module docstring for the partition/overlap matrix.  Packed and
    tile-sparse ``b`` support ``partition="column"`` only (their static
    layouts differ per shard, which rules out a single SPMD program);
    dense ``b`` supports all three, with ``overlap`` selecting the chunked
    ring schedule or the blocking-collective baseline.
    """
    if partition not in PARTITIONS:
        raise ValueError(f"partition must be one of {PARTITIONS}, "
                         f"got {partition!r}")
    if overlap not in OVERLAPS:
        raise ValueError(f"overlap must be one of {OVERLAPS}, "
                         f"got {overlap!r}")
    size = mesh_axis_size(mesh, axis)
    tag = mesh_plan_tag(mesh, axis)
    kw = dict(policy=policy, backend=backend, out_dtype=out_dtype)

    if is_packed(b) or is_sparse(b):
        if partition != "column":
            raise NotImplementedError(
                f"packed/sparse operands shard along N only "
                f"(partition='column'), got partition={partition!r}")
        return _column_parts(x, b, bias, mesh=mesh, axis=axis, size=size,
                             tag=tag, **kw)

    if b.ndim != 2:
        raise ValueError(f"mp_dot_sharded expects a 2-D dense b, got "
                         f"shape {b.shape}")
    m, k = x.shape

    if partition == "column":
        _check_div("N", b.shape[1], size)

        def body(xl, bl, biasl):
            return mp_dot(xl, bl, biasl, **kw)

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, None), P(None, axis), P(axis)),
                          out_specs=P(None, axis), check_vma=False)
        with mesh_namespace(tag):
            return f(x, b, _bias_or_empty(bias, b.shape[1]))

    if partition == "row":
        _check_div("K", k, size)
        if overlap == "ring":
            # The ring emits the reduced result one N-chunk per device.
            _check_div("N (ring chunking)", b.shape[1], size)

        # Partial K-contributions must accumulate across devices in f32 —
        # ring hops (or the psum) would otherwise round at the policy's
        # output precision once per step.
        def dot(xl, bl):
            return mp_dot(xl, bl, policy=policy, backend=backend,
                          out_dtype=jnp.float32)

        if overlap == "ring":
            body = _ring_row_body(axis, size, dot)
            out_spec = P(None, axis)
        else:
            def body(xl, bl):
                return jax.lax.psum(dot(xl, bl), axis)
            out_spec = P(None, None)
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, axis), P(axis, None)),
                          out_specs=out_spec, check_vma=False)
        with mesh_namespace(tag):
            y = f(x, b)
        return _finish(y, bias, out_dtype, policy)

    # partition == "gather": x M-sharded, b N-sharded, out (M, N) N-sharded.
    _check_div("M", m, size)
    _check_div("N", b.shape[1], size)

    def dot(xl, bl):
        return mp_dot(xl, bl, policy=policy, backend=backend,
                      out_dtype=jnp.float32)

    if overlap == "ring":
        body = _ring_gather_body(axis, size, dot)
    else:
        def body(xl, bl):
            full = jax.lax.all_gather(xl, axis, axis=0, tiled=True)
            return dot(full, bl)
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(axis, None), P(None, axis)),
                      out_specs=P(None, axis), check_vma=False)
    with mesh_namespace(tag):
        y = f(x, b)
    return _finish(y, bias, out_dtype, policy)


def _bias_or_empty(bias: Optional[jax.Array], n: int) -> jax.Array:
    # shard_map wants a concrete operand per in_spec; a (N,) zero bias is
    # free after fusion and keeps one program for both cases.
    return bias if bias is not None else jnp.zeros((n,), jnp.float32)


def _finish(y: jax.Array, bias: Optional[jax.Array], out_dtype,
            policy) -> jax.Array:
    """Bias + output cast for the reduction partitions (row/gather), where
    bias can only be applied to the fully reduced result."""
    if bias is not None:
        y = y + bias[None, :].astype(y.dtype)
    from repro.core.policy import get_policy
    tgt = out_dtype if out_dtype is not None else get_policy(policy).out_dtype
    return y.astype(tgt)


def _column_parts(x, b, bias, *, mesh, axis, size, tag, **kw):
    """Packed/tile-sparse column partition: per-shard programs.

    Each shard's GEMM traces with its LOCAL (m, n_local, k) — so the block
    planner and plan cache see per-shard shapes — inside the mesh plan
    namespace.  The concatenated output carries a sharding constraint;
    under jit over the mesh, GSPMD back-propagates it so each part's
    payload and compute stay on that shard's devices.
    """
    parts = shard_operand(b, size, axis="n")
    nl = parts[0].layout.n
    outs = []
    with mesh_namespace(tag):
        for s, bs in enumerate(parts):
            bias_s = bias[s * nl:(s + 1) * nl] if bias is not None else None
            outs.append(mp_dot(x, bs, bias_s, **kw))
    y = jnp.concatenate(outs, axis=-1)
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(None, axis)))


# ------------------------------------------------------------ grouped MoE

def mp_dot_grouped_sharded(
    x: jax.Array,
    b: Operand,
    bias: Optional[jax.Array] = None,
    *,
    mesh,
    axis: str = "model",
    group_sizes: Optional[jax.Array] = None,
    policy="bf16",
    backend: Optional[str] = None,
    out_dtype=None,
) -> jax.Array:
    """Expert-parallel ``mp_dot_grouped``: experts sharded, tokens routed.

    Dense ``b`` (G, K, N) runs the all-to-all dispatch/combine pair inside
    one ``shard_map`` (weights never move; each device runs the grouped
    MPGEMM over its G/P experts with the full token set for those experts).
    Packed/sparse grouped operands shard along G as per-shard programs
    (static layouts differ per shard — same constraint as the 2-D column
    partition).  The ragged ``group_sizes`` mask is applied on the global
    output, mirroring ``mp_dot_grouped``'s outside-the-VJP masking.
    """
    size = mesh_axis_size(mesh, axis)
    tag = mesh_plan_tag(mesh, axis)
    kw = dict(policy=policy, backend=backend, out_dtype=out_dtype)
    if x.ndim != 3:
        raise ValueError(f"expects x of rank 3 (G, M, K), got {x.shape}")
    g, m, _ = x.shape

    if is_packed(b) or is_sparse(b):
        y = _ep_parts(x, b, bias, mesh=mesh, axis=axis, size=size, tag=tag,
                      **kw)
    else:
        if b.ndim != 3:
            raise ValueError(f"expects dense b of rank 3 (G, K, N), got "
                             f"shape {b.shape}")
        _check_div("G", g, size)
        _check_div("M (token sharding)", m, size)

        def body(xl, bl, biasl):
            # dispatch: token-sharded (G, M/P, K) -> expert-sharded
            # (G/P, M, K); every token reaches the device owning its expert.
            xr = jax.lax.all_to_all(xl, axis, split_axis=0, concat_axis=1,
                                    tiled=True)
            yl = mp_dot_grouped(xr, bl, biasl, **kw)
            # combine: back to token sharding for the caller's next op.
            return jax.lax.all_to_all(yl, axis, split_axis=1, concat_axis=0,
                                      tiled=True)

        bias_full = (bias if bias is not None
                     else jnp.zeros((g, b.shape[-1]), jnp.float32))
        if bias_full.ndim == 1:
            bias_full = jnp.broadcast_to(bias_full[None, :],
                                         (g, bias_full.shape[0]))
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, axis, None), P(axis, None, None),
                      P(axis, None)),
            out_specs=P(None, axis, None), check_vma=False)
        with mesh_namespace(tag):
            y = f(x, b, bias_full)

    if group_sizes is not None:
        sizes = jnp.asarray(group_sizes, jnp.int32).reshape(-1, 1, 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        y = jnp.where(rows < sizes, y, jnp.zeros_like(y))
    return y


def _ep_parts(x, b, bias, *, mesh, axis, size, tag, **kw):
    """Expert-parallel packed/sparse path: per-shard grouped programs over
    G/P experts each, concatenated under an expert-sharded constraint."""
    g = x.shape[0]
    _check_div("G", g, size)
    gl = g // size
    parts = shard_operand(b, size, axis="g")
    outs = []
    with mesh_namespace(tag):
        for s, bs in enumerate(parts):
            xs = x[s * gl:(s + 1) * gl]
            bias_s = bias[s * gl:(s + 1) * gl] if (
                bias is not None and bias.ndim == 2) else bias
            if gl == 1:
                # shard_operand squeezed the group axis (layout g=1);
                # run the single expert as a 2-D GEMM and restore the axis.
                b2 = bias_s[0] if (bias_s is not None
                                  and bias_s.ndim == 2) else bias_s
                outs.append(mp_dot(xs[0], bs, b2, **kw)[None])
            else:
                outs.append(mp_dot_grouped(xs, bs, bias_s, **kw))
    y = jnp.concatenate(outs, axis=0)
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(axis, None, None)))
