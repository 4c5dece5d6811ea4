"""Analytic block-size planner for MPGEMM-TPU.

This is the TPU adaptation of the paper's cache-aware partitioning model
(Section IV-B, equations (1)-(3)):

  paper eq (1): working set of packed blocks < shared-L2 (8 MB)
      -> here: double-buffered A/B input blocks + resident accumulator must
         fit the VMEM budget.

  paper eq (2): TLB-entry bound on kc
      -> no TLB on TPU.  Replaced by a DMA-granularity bound: every block's
         minor (lane) dimension must span >= ``min_dma_row_bytes`` contiguous
         bytes, the analogue of issuing four-Z-register (256 B) grouped loads
         instead of single-Z (64 B) loads.

  paper eq (3): maximize compute-to-memory ratio (CMR)
      -> same objective.  For a K-innermost revisiting grid the total HBM
         traffic is
            bytes = A_bytes * ceil(N/bn) + B_bytes * ceil(M/bm) + C_bytes
         so CMR maximization == traffic minimization.  We solve the
         continuous relaxation (Lagrange: bm == bn at the optimum, bk as
         large as capacity allows) and then refine over the hardware-aligned
         discrete lattice, mirroring the paper's "analytical model + final
         alignment to mr/nr".

The planner emits a :class:`GemmPlan` consumed by ``kernels/mpgemm.py`` (as
BlockSpec shapes) and by benchmarks (as the predicted-traffic model).

The analytic model is deliberately open-loop — it never sees a measurement.
``repro.tuning`` closes the loop: :func:`enumerate_block_lattice` exposes the
exact candidate lattice the planner searches, :func:`plan_with_blocks` prices
an arbitrary lattice point, and :func:`plan_to_dict` / :func:`plan_from_dict`
let tuned plans persist across processes (tuning/plan_cache.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.codecs import dtype_bits, dtype_bytes, get_codec, plan_dtype
from repro.core.constants import DEFAULT_HW, HardwareSpec


def _dtype_bytes(dtype):
    """Bytes per element — fractional for sub-byte payload codecs (int4
    moves half a byte of HBM per weight element; core/codecs.py)."""
    return dtype_bytes(dtype)


def _min_span(dtype, floor_bytes: int, align: int) -> int:
    """Smallest ``align``-multiple element count whose contiguous row
    covers ``floor_bytes`` — computed in BITS so sub-byte codecs get an
    exact integer answer (int4: 512 B -> 1024 elements)."""
    bits = dtype_bits(dtype)
    elems = (floor_bytes * 8 + bits - 1) // bits
    return max(align, _round_up(elems, align))


def _sublane(hw: HardwareSpec, dtype) -> int:
    """Second-minor granularity; sub-byte codecs tile like their storage
    bytes (int4 nibbles live in int8 bytes -> the (32, 128) int8 tile)."""
    return hw.sublane(max(1, dtype_bits(dtype) // 8))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _round_down(x: int, m: int) -> int:
    return max(m, (x // m) * m)


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """A fully-specified blocking decision for one (possibly grouped) GEMM.

    ``g > 1`` marks a grouped/batched instance: G independent M x N x K
    problems executed by one kernel launch with the group as the leading
    grid axis.  ``grid`` stays the per-group (M/bm, N/bn, K/bk) triple (the
    kernel prepends G); ``flops``/``hbm_bytes`` cover all G groups, so the
    roofline and CMR terms price the whole launch.
    """

    m: int
    n: int
    k: int
    bm: int
    bn: int
    bk: int
    a_dtype: str
    b_dtype: str
    out_dtype: str
    acc_dtype: str
    # Derived.
    grid: Tuple[int, int, int]
    vmem_bytes: int          # modeled VMEM working set
    hbm_bytes: int           # modeled HBM traffic for the whole GEMM
    flops: int               # 2*G*M*N*K
    cmr: float               # flops / hbm_bytes (the paper's eq (3) value)
    k_rem: int               # K % bk (0 -> no K-edge predication needed)
    notes: str = ""
    g: int = 1               # group/batch count (1 == plain 2-D GEMM)

    @property
    def arithmetic_intensity(self) -> float:
        return self.cmr

    def describe(self) -> str:
        shape = f"{self.m}x{self.n}x{self.k}"
        if self.g != 1:
            shape = f"{self.g}x" + shape
        return (
            f"GemmPlan[{shape} {self.a_dtype}->"
            f"{self.out_dtype}] blocks=({self.bm},{self.bn},{self.bk}) "
            f"grid={self.grid} vmem={self.vmem_bytes/2**20:.2f}MiB "
            f"CMR={self.cmr:.1f} {self.notes}"
        )


def _resolve_dtypes(a_dtype, b_dtype=None, out_dtype=None, acc_dtype=None):
    """Canonical (a, b, out, acc) dtype strings under the policy defaults.

    int inputs accumulate in int32 and default to an int32 output; float
    inputs accumulate in f32 and default to the input dtype out (the MXU's
    native pairs, paper Section V).  A payload-codec B dtype (``int4`` /
    ``fp8e4m3``) passes through verbatim — the codec name IS the pricing
    and cache-key namespace — and defaults the accumulator to f32 (the
    per-tile dequant accumulates dequantized partials).
    """
    b_dtype = b_dtype or a_dtype
    b_codec = get_codec(b_dtype)
    out_dtype = out_dtype or ("int32" if jnp.dtype(a_dtype).kind == "i" else a_dtype)
    if acc_dtype is None:
        if b_codec is not None and b_codec.name != "int8":
            acc_dtype = "float32"
        else:
            acc_dtype = "int32" if jnp.dtype(a_dtype).kind == "i" else "float32"
    return (
        str(jnp.dtype(a_dtype)), plan_dtype(b_dtype),
        str(jnp.dtype(out_dtype)), str(jnp.dtype(acc_dtype)),
    )


def enumerate_block_lattice(
    m: int,
    n: int,
    k: int,
    a_dtype="float32",
    b_dtype=None,
    *,
    hw: HardwareSpec = DEFAULT_HW,
    max_block: int = 2048,
) -> Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]:
    """The hardware-aligned candidate lattice (bm, bn, bk) the planner searches.

    Each axis is a power-of-two ladder from the granularity floor (sublane /
    lane / DMA-row-width alignment — the paper's P2 wide-load constraint) up
    to ``max_block``, plus an exact-fit candidate for small dims (the edge
    micro-kernel choice).  ``repro.tuning.microbench`` sweeps this same
    lattice so measured plans can never leave the space the kernel supports.
    """
    a_dtype, b_dtype, _, _ = _resolve_dtypes(a_dtype, b_dtype)
    lane = hw.lane
    min_bk = _min_span(a_dtype, hw.min_dma_row_bytes, lane)
    min_bn = _min_span(b_dtype, hw.min_dma_row_bytes, lane)
    sub_a = _sublane(hw, a_dtype)
    sub_b = _sublane(hw, b_dtype)

    def _cands(minimum: int, align: int, dim: int):
        out = []
        v = minimum
        while v <= min(max_block, _round_up(dim, align)):
            out.append(v)
            v *= 2
        exact = _round_up(dim, align)
        if exact <= max_block and exact not in out:
            out.append(exact)
        return sorted(set(out))

    bm_cands = _cands(max(sub_a, min(128, _round_up(m, sub_a))), sub_a, m)
    bm_cands = [c for c in bm_cands if c <= _round_up(m, sub_a)]
    bn_cands = [c for c in _cands(min_bn, lane, n) if c <= _round_up(n, lane)]
    bk_align = max(lane, sub_b)
    bk_cands = [c for c in _cands(min_bk, bk_align, k) if c <= _round_up(k, bk_align)]
    return tuple(bm_cands), tuple(bn_cands), tuple(bk_cands)


def modeled_traffic_bytes(
    m: int, n: int, k: int, bm: int, bn: int,
    a_bytes: float, b_bytes: float, c_bytes: float, beta: float = 0.0,
    extra_mn_inputs: int = 0, density: float = 1.0,
) -> int:
    """HBM traffic for a K-innermost revisiting grid (C resident in VMEM).

    A is re-read once per column-block of C; B once per row-block of C; C is
    written once (and read once iff beta != 0).  ``extra_mn_inputs`` counts
    additional (M, N)-shaped epilogue operands (gated-activation / residual
    fusions — core/gemm_spec.py), each read exactly once.  The per-element
    byte counts may be FRACTIONAL: sub-byte payload codecs (int4) price by
    bits-per-element, so a nibble-packed B stream costs 0.5 bytes/element.

    ``density`` < 1 prices a TILE-SPARSE B operand (repro.sparse): only the
    stored fraction of B tiles is ever DMA'd, and the A-side re-reads
    shrink the same way (the sparse walk skips the A block of a pruned
    (kk, j) tile too — grid steps, not just payload bytes, scale with
    density).  The epilogue/C terms do NOT scale: every output tile is
    still visited (anchor visits) and written exactly once.
    """
    n_col_blocks = math.ceil(n / bn)
    n_row_blocks = math.ceil(m / bm)
    c_factor = 2 if beta else 1
    return int(
        m * k * a_bytes * n_col_blocks * density
        + k * n * b_bytes * n_row_blocks * density
        + m * n * c_bytes * (c_factor + extra_mn_inputs)
    )


def decode_bytes_per_element(a_dtype, b_dtype) -> int:
    """VMEM bytes per B element that the kernel's in-register payload
    decode materializes (``kernels/mpgemm.py::_accumulate``).

    int4 nibbles unpack through int32 into int8 rows (``unpack_nibbles``);
    a quantized tile against a float A is scaled in f32 and cast to A's
    dtype before the dot.  Dense and int x int8 paths decode nothing.
    """
    codec = get_codec(b_dtype)
    if codec is None:
        return 0
    n = 4 + 1 if codec.elems_per_byte > 1 else 0
    a = jnp.dtype(a_dtype)
    if a.kind != "i":
        n += 4 + a.itemsize
    elif not codec.integer:
        n += 4              # int A x float payload: both sides upcast
    return n


def vmem_working_set(
    bm: int, bn: int, bk: int,
    a_bytes: int, b_bytes: int, out_bytes: int, acc_bytes: int = 4,
    beta: float = 0.0, extra_mn_inputs: int = 0, decode_bytes: int = 0,
) -> int:
    """Paper eq (1), VMEM form.

    The paper reserves space for the *next* iteration's Bc and the C block on
    top of the current blocks (LRU anti-eviction).  The TPU analogue is the
    Pallas pipeline's double buffering of the streamed inputs, plus the
    resident accumulator and the output staging block.  Each extra
    (M, N)-shaped epilogue operand (gated/residual fusions) streams one more
    double-buffered (bm, bn) block.  ``decode_bytes`` prices the in-VMEM
    temporaries of a quantized payload's decode per (bk, bn) element
    (:func:`decode_bytes_per_element`).
    """
    dbuf = 2  # double-buffered HBM->VMEM pipeline
    ws = dbuf * (bm * bk * a_bytes + bk * bn * b_bytes)
    ws += bk * bn * decode_bytes       # payload decode temporaries
    ws += bm * bn * acc_bytes          # resident accumulator (the "ZA tiles")
    ws += bm * bn * out_bytes          # output staging
    if beta:
        ws += dbuf * bm * bn * out_bytes   # streamed C input blocks
    ws += extra_mn_inputs * dbuf * bm * bn * out_bytes  # epilogue operands
    return int(ws)


def plan_gemm(
    m: int,
    n: int,
    k: int,
    a_dtype="float32",
    b_dtype=None,
    out_dtype=None,
    acc_dtype=None,
    *,
    beta: float = 0.0,
    extra_mn_inputs: int = 0,
    density: float = 1.0,
    hw: HardwareSpec = DEFAULT_HW,
    vmem_budget_frac: float = 0.75,
    max_block: int = 2048,
) -> GemmPlan:
    """Pick (bm, bn, bk) for an M x N x K GEMM.

    Mirrors the paper's flow: fix the register-level micro tile from the ISA
    (here the MXU's 128), derive the reduction block from the granularity
    constraint (paper: TLB eq (2); here: DMA row width), then maximize CMR
    subject to the capacity constraint (paper: 8 MB L2; here: VMEM budget).

    ``density`` < 1 prices a tile-sparse B operand (repro.sparse): skipped
    tiles cost neither HBM bytes (A and B streams scale with density) nor
    MACs (FLOPs scale the same way), so the CMR objective — and therefore
    the chosen blocks — reflects the sparse launch the plan will serve.
    """
    a_dtype, b_dtype, out_dtype, acc_dtype = _resolve_dtypes(
        a_dtype, b_dtype, out_dtype, acc_dtype
    )
    ab = _dtype_bytes(a_dtype)
    bb = _dtype_bytes(b_dtype)
    ob = _dtype_bytes(out_dtype)
    accb = _dtype_bytes(acc_dtype)

    budget = int(hw.vmem_bytes * vmem_budget_frac)
    dec = decode_bytes_per_element(a_dtype, b_dtype)
    lane = hw.lane
    sub_a = _sublane(hw, a_dtype)   # A/acc second-minor granularity
    sub_b = _sublane(hw, b_dtype)   # B second-minor granularity (bk)
    bk_align = max(lane, sub_b)

    # Granularity floors (paper P2: four-Z-register loads) are baked into the
    # lattice: minor-dim spans cover >= min_dma_row_bytes of contiguous data.
    bm_cands, bn_cands, bk_cands = enumerate_block_lattice(
        m, n, k, a_dtype, b_dtype, hw=hw, max_block=max_block
    )

    best = None
    for bm in bm_cands:
        for bn in bn_cands:
            for bk in bk_cands:
                ws = vmem_working_set(bm, bn, bk, ab, bb, ob, accb, beta,
                                      extra_mn_inputs, dec)
                if ws > budget:
                    continue
                traffic = modeled_traffic_bytes(m, n, k, bm, bn, ab, bb, ob,
                                                beta, extra_mn_inputs,
                                                density)
                flops = int(2 * m * n * k * density)
                cmr = flops / max(1, traffic)
                # Secondary objectives: fewer grid steps, squarer C block.
                grid_steps = (
                    math.ceil(m / bm) * math.ceil(n / bn) * math.ceil(k / bk)
                )
                key = (cmr, -grid_steps, min(bm, bn))
                if best is None or key > best[0]:
                    best = (key, (bm, bn, bk, ws, traffic, cmr))
    if best is None:
        # Degenerate fallback: smallest aligned blocks.
        bm, bn, bk = sub_a, lane, bk_align
    else:
        bm, bn, bk = best[1][:3]
    return plan_with_blocks(
        m, n, k, bm, bn, bk, a_dtype, b_dtype, out_dtype, acc_dtype,
        beta=beta, extra_mn_inputs=extra_mn_inputs, density=density, hw=hw,
    )


def plan_with_blocks(
    m: int,
    n: int,
    k: int,
    bm: int,
    bn: int,
    bk: int,
    a_dtype="float32",
    b_dtype=None,
    out_dtype=None,
    acc_dtype=None,
    *,
    beta: float = 0.0,
    extra_mn_inputs: int = 0,
    density: float = 1.0,
    hw: HardwareSpec = DEFAULT_HW,
    notes: str = "",
) -> GemmPlan:
    """Price one lattice point: a :class:`GemmPlan` for *forced* (bm, bn, bk).

    Blocks are clamped to the problem's aligned extent and all derived model
    terms (grid, VMEM working set, HBM traffic, CMR, K-edge predication) are
    recomputed, so a tuned plan carries the same metadata as an analytic one.
    The autotuner (repro.tuning) is the main caller.
    """
    a_dtype, b_dtype, out_dtype, acc_dtype = _resolve_dtypes(
        a_dtype, b_dtype, out_dtype, acc_dtype
    )
    ab = _dtype_bytes(a_dtype)
    bb = _dtype_bytes(b_dtype)
    ob = _dtype_bytes(out_dtype)
    accb = _dtype_bytes(acc_dtype)
    sub_a = _sublane(hw, a_dtype)
    bk_align = max(hw.lane, _sublane(hw, b_dtype))

    bm = min(bm, _round_up(m, sub_a))
    bn = min(bn, _round_up(n, hw.lane))
    bk = min(bk, _round_up(k, bk_align))
    ws = vmem_working_set(bm, bn, bk, ab, bb, ob, accb, beta,
                          extra_mn_inputs,
                          decode_bytes_per_element(a_dtype, b_dtype))
    traffic = modeled_traffic_bytes(m, n, k, bm, bn, ab, bb, ob, beta,
                                    extra_mn_inputs, density)
    flops = int(2 * m * n * k * density)
    grid = (math.ceil(m / bm), math.ceil(n / bn), math.ceil(k / bk))
    auto_notes = [notes] if notes else []
    if density < 1.0:
        auto_notes.append(f"density={density:.2f}")
    if m % bm or n % bn:
        auto_notes.append("edge-mn")
    k_rem = k % bk
    if k_rem:
        auto_notes.append("edge-k(predicated)")
    return GemmPlan(
        m=m, n=n, k=k, bm=bm, bn=bn, bk=bk,
        a_dtype=a_dtype, b_dtype=b_dtype,
        out_dtype=out_dtype, acc_dtype=acc_dtype,
        grid=grid, vmem_bytes=ws, hbm_bytes=traffic, flops=flops,
        cmr=flops / max(1, traffic), k_rem=k_rem,
        notes=" ".join(auto_notes),
    )


def grouped_plan_from_2d(plan: GemmPlan, g: int) -> GemmPlan:
    """Lift a 2-D plan to a G-group batched one (group = leading grid axis).

    Groups are independent problems streamed back-to-back, so there is no
    cross-group reuse to model: per-group traffic and FLOPs simply scale by
    G (CMR is invariant), and the VMEM working set is unchanged — each grid
    step still stages one (bm, bk)/(bk, bn) input pair and one (bm, bn)
    accumulator, now for whichever group the leading grid index names.
    """
    if g < 1:
        raise ValueError(f"group count must be >= 1, got {g}")
    if g == 1:
        return plan
    notes = " ".join(x for x in (plan.notes, f"grouped(g={g})") if x)
    return dataclasses.replace(
        plan, g=g, flops=plan.flops * g, hbm_bytes=plan.hbm_bytes * g,
        notes=notes,
    )


def plan_grouped_gemm(
    g: int,
    m: int,
    n: int,
    k: int,
    a_dtype="float32",
    b_dtype=None,
    out_dtype=None,
    acc_dtype=None,
    *,
    beta: float = 0.0,
    hw: HardwareSpec = DEFAULT_HW,
    vmem_budget_frac: float = 0.75,
    max_block: int = 2048,
) -> GemmPlan:
    """Block plan for a grouped GEMM: G independent M x N x K problems.

    The per-group blocking solve is exactly the 2-D one — the group axis
    adds grid steps, not working set — so the analytic optimum is the 2-D
    optimum lifted by :func:`grouped_plan_from_2d`.  Consumed by
    ``kernels/mpgemm.py::mpgemm_grouped_pallas`` (grid ``(G, M/bm, N/bn,
    K/bk)``) and priced by the MoE-workload benchmarks.
    """
    base = plan_gemm(
        m, n, k, a_dtype, b_dtype, out_dtype, acc_dtype,
        beta=beta, hw=hw, vmem_budget_frac=vmem_budget_frac,
        max_block=max_block,
    )
    return grouped_plan_from_2d(base, g)


def plan_to_dict(plan: GemmPlan) -> dict:
    """JSON-safe dict form of a plan (tuning/plan_cache.py wire format)."""
    d = dataclasses.asdict(plan)
    d["grid"] = list(plan.grid)
    return d


def plan_from_dict(d: dict) -> GemmPlan:
    """Inverse of :func:`plan_to_dict`."""
    d = dict(d)
    d["grid"] = tuple(d["grid"])
    return GemmPlan(**d)


def naive_plan(m: int, n: int, k: int, a_dtype="float32", **kw) -> GemmPlan:
    """The 'three-level loop, fixed tile' baseline the paper ablates against.

    Fixed 256^3 blocks regardless of shape or dtype — the analogue of the
    baselines' fixed micro-tile + single-matrix packing.  Used by
    benchmarks/bench_breakdown.py.
    """
    plan = plan_gemm(m, n, k, a_dtype, **kw)
    bm = min(256, _round_up(m, 8))
    bn = min(256, _round_up(n, 128))
    bk = min(256, _round_up(k, 128))
    ab = _dtype_bytes(plan.a_dtype)
    bb = _dtype_bytes(plan.b_dtype)
    ob = _dtype_bytes(plan.out_dtype)
    traffic = modeled_traffic_bytes(m, n, k, bm, bn, ab, bb, ob)
    return dataclasses.replace(
        plan, bm=bm, bn=bn, bk=bk,
        grid=(math.ceil(m / bm), math.ceil(n / bn), math.ceil(k / bk)),
        vmem_bytes=vmem_working_set(bm, bn, bk, ab, bb, ob),
        hbm_bytes=traffic, cmr=2 * m * n * k / max(1, traffic),
        k_rem=k % bk, notes="naive-256^3",
    )
