"""mp_dot_grouped / mpgemm_grouped_pallas: einsum equivalence across the
precision policies, ragged groups via masking, fused-transpose VJP, and the
grouped plan/cache plumbing; mpgemm_ragged_pallas / mp_dot_ragged: the
group-sorted ragged launch against a per-segment einsum."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.blocking import plan_gemm, plan_grouped_gemm
from repro.core.gemm import mp_dot_grouped, mp_dot_ragged, mp_einsum
from repro.kernels.mpgemm import (
    mpgemm_grouped_pallas, mpgemm_ragged_pallas, ragged_block_rows,
    ragged_layout, ragged_rows,
)
from repro.tuning import PlanCache, make_key, set_plan_cache, tune_grouped_gemm

G, M, K, N = 4, 24, 40, 24


@pytest.fixture
def ops(rng):
    x = jnp.asarray(rng.standard_normal((G, M, K)), "float32")
    w = jnp.asarray(rng.standard_normal((G, K, N)), "float32")
    return x, w


def _ref(x, w):
    return jnp.einsum("gmk,gkn->gmn", x.astype(jnp.float32),
                      w.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("policy", ["fp32", "bf16", "int8"])
def test_forward_matches_einsum_reference(ops, policy, backend):
    x, w = ops
    y = mp_dot_grouped(x, w, policy=policy, backend=backend)
    ref = np.asarray(_ref(x, w))
    got = np.asarray(y, np.float32)
    if policy == "fp32":
        np.testing.assert_allclose(got, ref, atol=1e-5)
    elif policy == "bf16":
        np.testing.assert_allclose(got, ref, atol=0.15)  # bf16 mantissa
    else:  # int8 dynamic per-tensor: bounded relative error vs fp32
        assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max()


@pytest.mark.parametrize("policy", ["fp32", "bf16", "int8"])
def test_backends_agree(ops, policy):
    x, w = ops
    a = mp_dot_grouped(x, w, policy=policy, backend="xla")
    b = mp_dot_grouped(x, w, policy=policy, backend="interpret")
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=1e-5, rtol=1e-4)


def test_trans_w_matches_einsum(ops):
    x, w = ops
    wt = jnp.swapaxes(w, 1, 2)  # stored (G, N, K)
    y = mp_dot_grouped(x, wt, policy="fp32", trans_w=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_ref(x, w)),
                               atol=1e-5)


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
def test_vjp_matches_einsum(ops, policy):
    x, w = ops

    def f1(x, w):
        return jnp.sum(mp_dot_grouped(x, w, policy=policy,
                                      out_dtype=jnp.float32) ** 2)

    def f2(x, w):
        cd = jnp.float32 if policy == "fp32" else jnp.bfloat16
        return jnp.sum(jnp.einsum(
            "gmk,gkn->gmn", x.astype(cd), w.astype(cd),
            preferred_element_type=jnp.float32) ** 2)

    g1 = jax.grad(f1, (0, 1))(x, w)
    g2 = jax.grad(f2, (0, 1))(x, w)
    tol = 1e-4 if policy == "fp32" else 0.35  # bf16 bwd partial sums
    scale = max(float(jnp.abs(g2[0]).max()), 1.0)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol * scale)


def test_int8_vjp_is_ste_and_finite(ops):
    """int8 backward runs in the bf16 sibling (straight-through estimator)."""
    x, w = ops
    g = jax.grad(lambda w: jnp.sum(
        mp_dot_grouped(x, w, policy="int8") ** 2))(w)
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).sum()) > 0


def test_ragged_groups_mask_output_and_grads(ops):
    x, w = ops
    sizes = jnp.asarray([M, 10, 0, 17], jnp.int32)
    y = mp_dot_grouped(x, w, policy="fp32", group_sizes=sizes)
    ref = np.asarray(_ref(x, w))
    for gi, s in enumerate([M, 10, 0, 17]):
        assert np.all(np.asarray(y[gi, s:]) == 0.0)
        np.testing.assert_allclose(np.asarray(y[gi, :s]), ref[gi, :s],
                                   atol=1e-5)
    # masked rows contribute no gradient; group 2 (size 0) none at all
    dx = jax.grad(lambda x: jnp.sum(mp_dot_grouped(
        x, w, policy="fp32", group_sizes=sizes) ** 2))(x)
    assert np.all(np.asarray(dx[2]) == 0.0)
    assert np.all(np.asarray(dx[1, 10:]) == 0.0)
    assert float(jnp.abs(dx[0]).sum()) > 0


def test_bias_forward_and_grad(ops):
    x, w = ops
    bias = jnp.ones((G, N), jnp.float32)
    y = mp_dot_grouped(x, w, bias, policy="fp32")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ref(x, w)) + 1.0, atol=1e-5)
    db = jax.grad(lambda b: jnp.sum(
        mp_dot_grouped(x, w, b, policy="fp32")))(bias)
    np.testing.assert_allclose(np.asarray(db), float(M), atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_shared_1d_bias_all_backends_and_grad(ops, backend):
    """A shared (N,) bias broadcasts to every group on both backends, and
    its gradient sum-reduces back to (N,)."""
    x, w = ops
    bias = jnp.arange(N, dtype=jnp.float32)
    y = mp_dot_grouped(x, w, bias, policy="fp32", backend=backend)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ref(x, w)) + np.arange(N),
                               atol=1e-5)
    db = jax.grad(lambda b: jnp.sum(mp_dot_grouped(
        x, w, b, policy="fp32", backend=backend)))(bias)
    assert db.shape == (N,)
    np.testing.assert_allclose(np.asarray(db), float(G * M), atol=1e-4)


def test_static_int8_weights_under_int8_policy(ops):
    """Static {"q","scale"} expert weights must dequantize to float (not the
    int8 policy's own compute dtype) before dynamic re-quantization."""
    from repro.core.quantization import quantize_tensor
    x, w = ops
    wq = quantize_tensor(w * 0.01)   # small scale: int8 truncation would zero it
    ref = np.asarray(_ref(x, w * 0.01))
    y = np.asarray(mp_dot_grouped(x, wq, policy="int8"), np.float32)
    assert np.abs(y).max() > 0.1 * np.abs(ref).max()   # not collapsed to ~0
    assert np.abs(y - ref).max() < 0.1 * np.abs(ref).max()


def test_grad_wrt_x_with_static_int8_weights(ops):
    """grad through mp_dot_grouped must work when w is a static {"q","scale"}
    dict (the bwd rule contracts against the dequantized array, not the
    dict) — the serving-weights MoE configuration."""
    from repro.core.quantization import quantize_tensor
    x, w = ops
    wq = quantize_tensor(w)
    dx = jax.grad(lambda x: jnp.sum(
        mp_dot_grouped(x, wq, policy="bf16") ** 2))(x)
    assert dx.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(dx))) and float(jnp.abs(dx).sum()) > 0


def test_non_f32_bias_grad_dtype(ops):
    """dbias cotangent must match a non-f32 bias primal's dtype."""
    x, w = ops
    bias = jnp.ones((G, N), jnp.bfloat16)
    db = jax.grad(lambda b: jnp.sum(mp_dot_grouped(
        x, w, b, policy="bf16", out_dtype=jnp.float32)))(bias)
    assert db.dtype == jnp.bfloat16 and db.shape == (G, N)


def test_kernel_epilogue_fusion(rng):
    a = jnp.asarray(rng.standard_normal((3, 16, 48)), "float32")
    b = jnp.asarray(rng.standard_normal((3, 48, 24)), "float32")
    bias = jnp.asarray(rng.standard_normal((3, 24)), "float32")
    y = mpgemm_grouped_pallas(a, b, alpha=0.5, bias=bias, activation="relu",
                              interpret=True)
    ref = jax.nn.relu(0.5 * _ref(a, b) + bias[:, None, :])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)


def test_grouped_plan_scaling_and_key_namespace():
    p2 = plan_gemm(M, N, K, "float32")
    pg = plan_grouped_gemm(G, M, N, K, "float32")
    assert pg.g == G and (pg.bm, pg.bn, pg.bk) == (p2.bm, p2.bn, p2.bk)
    assert pg.flops == G * p2.flops and pg.hbm_bytes == G * p2.hbm_bytes
    assert pg.vmem_bytes == p2.vmem_bytes          # group adds no working set
    assert abs(pg.cmr - p2.cmr) < 1e-9             # CMR is g-invariant
    k2 = make_key(M, N, K, "float32")
    kg = make_key(M, N, K, "float32", g=G)
    assert kg != k2 and kg.startswith(f"g{G}|")
    assert make_key(M, N, K, "float32", g=1) == k2  # 2-D schema unchanged


def test_tuned_grouped_plan_is_consumed(ops):
    """tune_grouped_gemm persists under the grouped key; mp_dot_grouped
    picks the tuned plan up transparently with identical numerics."""
    x, w = ops
    cache = PlanCache(None)
    res = tune_grouped_gemm(G, M, N, K, "float32", mode="modeled",
                            max_candidates=4, cache=cache)
    assert res.best.plan.g == G
    assert res.key in cache and cache.get(res.key).g == G
    baseline = mp_dot_grouped(x, w, policy="fp32", backend="interpret")
    prev = set_plan_cache(cache)
    try:
        tuned = mp_dot_grouped(x, w, policy="fp32", backend="interpret")
    finally:
        set_plan_cache(prev)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(baseline),
                               atol=1e-6)


def test_mp_einsum_routes_grouped_specs(ops):
    x, w = ops
    ref = np.asarray(_ref(x, w))
    y = mp_einsum("end,edf->enf", x, w, policy="fp32")
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)
    wt = jnp.swapaxes(w, 1, 2)
    y2 = mp_einsum("bij,bkj->bik", x, wt, policy="fp32")
    np.testing.assert_allclose(np.asarray(y2), ref, atol=1e-5)
    # non-grouped specs still take the einsum path (shape sanity only)
    att = mp_einsum("bhqd,bhkd->bhqk",
                    jnp.ones((2, 2, 4, 8)), jnp.ones((2, 2, 4, 8)),
                    policy="fp32")
    assert att.shape == (2, 2, 4, 4)


def _ragged_case(rng, counts, bm, k, n, x_dtype=jnp.bfloat16):
    """A group-sorted buffer for ``counts`` rows per group, laid out by
    ragged_layout: -> (x, w, tiles, group of each row (-1: padding))."""
    counts = np.asarray(counts, np.int32)
    g = len(counts)
    r = -(-int(counts.sum()) // bm) * bm + g * bm
    start, tiles = ragged_layout(jnp.asarray(counts), bm, r // bm)
    x = np.zeros((r, k), np.float32)
    grp = np.full((r,), -1)
    for gi, (s0, c) in enumerate(zip(np.asarray(start), counts)):
        x[s0:s0 + c] = rng.standard_normal((c, k))
        grp[s0:s0 + c] = gi
    w = rng.standard_normal((g, k, n)).astype(np.float32)
    return (jnp.asarray(x, x_dtype), jnp.asarray(w, jnp.bfloat16), tiles,
            grp)


def _segment_ref(x, w, grp):
    """float32 per-segment einsum over the rows that hold a group."""
    xf = np.asarray(x, np.float32)
    wf = np.asarray(w.astype(jnp.float32))
    ref = np.zeros((xf.shape[0], wf.shape[2]), np.float32)
    for gi in range(wf.shape[0]):
        rows = grp == gi
        ref[rows] = np.einsum("mk,kn->mn", xf[rows], wf[gi])
    return ref


@pytest.mark.parametrize("counts,bm,gated,x_dtype,col_blocks,layers", [
    ([5, 0, 12, 0, 3], 8, False, jnp.bfloat16, 1, 0),
    ([0, 0, 40, 0], 8, True, jnp.bfloat16, 1, 0),
    ([300, 0, 129, 1], 128, False, jnp.bfloat16, 1, 0),
    ([17, 140, 0, 0, 9], 128, True, jnp.float32, 1, 0),
    ([0, 0, 0], 8, False, jnp.bfloat16, 1, 0),
    ([20, 0, 3], 8, True, jnp.bfloat16, 2, 0),
    ([0, 11, 0, 30], 8, True, jnp.bfloat16, 1, 3),
], ids=["empty-groups-bm8", "one-group-all-rows-gated-bm8",
        "multi-tile-segments-bm128", "gated-f32-rows-bm128", "no-rows",
        "two-column-blocks-gated-bm8", "layer-of-a-stack-gated-bm8"])
def test_ragged_launch_matches_segment_einsum(rng, monkeypatch, counts, bm,
                                              gated, x_dtype, col_blocks,
                                              layers):
    """Rows of live tiles match a float32 einsum per segment, with f32
    out; the tile map holds fewer live tiles than the buffer's tiles.
    ``col_blocks`` 2 shrinks the weight VMEM budget so that the launch
    walks the tiles once per half of N; ``layers`` 3 hands the launch a
    stack of three layers' weights, of which it must read the second."""
    k, n = 64, 256
    if col_blocks > 1:
        monkeypatch.setattr("repro.kernels.mpgemm.RAGGED_WEIGHT_VMEM",
                            2 * k * (n // col_blocks) * 2)
    x, w, tiles, grp = _ragged_case(rng, counts, bm, k, n, x_dtype)
    n_live = int(tiles.live[0])
    assert n_live == sum(-(-c // bm) for c in counts)
    assert n_live < tiles.group.shape[0]
    ref = _segment_ref(x.astype(jnp.bfloat16), w, grp)
    gate = None
    if gated:
        gate = jnp.asarray(rng.standard_normal(ref.shape), jnp.float32)
        ref = np.asarray(jax.nn.silu(ref)) * np.asarray(gate)
    layer = None
    if layers:
        layer = jnp.int32(1)
        w = jnp.stack([-w if i != 1 else w for i in range(layers)])
    y = mpgemm_ragged_pallas(x, w, tiles, activation="silu" if gated
                             else None, gate=gate, layer=layer,
                             interpret=True)
    assert y.dtype == jnp.float32 and y.shape == (x.shape[0], n)
    rows = grp >= 0
    np.testing.assert_allclose(np.asarray(y)[rows], ref[rows],
                               rtol=1e-5, atol=1e-4)
    # Padding rows of live tiles multiply zero rows.
    pad = (grp < 0) & (np.arange(x.shape[0]) < n_live * bm)
    np.testing.assert_allclose(np.asarray(y)[pad], 0.0, atol=1e-6)


def test_ragged_tile_map():
    """Segments start on tile boundaries; each group's first tile names
    the next group with rows for the weight prefetch."""
    start, tiles = ragged_layout(jnp.asarray([3, 0, 17, 1, 0]), 8, 8)
    np.testing.assert_array_equal(start, [0, 8, 8, 32, 40])
    np.testing.assert_array_equal(tiles.group[:5], [0, 2, 2, 2, 3])
    np.testing.assert_array_equal(tiles.fetch, [2, 3, -1, -1, -1, -1, -1,
                                                -1])
    assert int(tiles.live[0]) == 5
    assert [ragged_block_rows(p, 32) for p in (8, 64, 512, 4096, 9000)] \
        == [8, 8, 16, 128, 128]
    assert ragged_rows(4096, 32) == 8192 and ragged_rows(64, 32) == 320


@pytest.mark.parametrize("policy", ["fp32", "bf16", "int8"])
def test_mp_dot_ragged_backends_agree(rng, policy):
    """The kernel (interpret) and the XLA per-tile grouped GEMM agree."""
    x, w, tiles, grp = _ragged_case(rng, [9, 0, 20], 8, 32, 128)
    w = w.astype(jnp.float32)
    a = mp_dot_ragged(x, w, tiles, policy=policy, backend="xla",
                      out_dtype=jnp.float32)
    b = mp_dot_ragged(x, w, tiles, policy=policy, backend="interpret",
                      out_dtype=jnp.float32)
    rows = grp >= 0
    np.testing.assert_allclose(np.asarray(a)[rows], np.asarray(b)[rows],
                               rtol=1e-5, atol=1e-4)
    ref = _segment_ref(x, w, grp)[rows]
    err = np.abs(np.asarray(a)[rows] - ref).max()
    assert err < (1e-4 if policy != "int8" else 0.05 * np.abs(ref).max())


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("stack_dtype", [jnp.bfloat16, jnp.float32])
def test_mp_dot_ragged_reads_one_layer_of_a_stack(rng, backend, stack_dtype):
    """``layer`` picks one layer of an (L, G, K, N) stack, read in place
    by the kernel when the stack is in the compute dtype and sliced
    first otherwise; the rows equal those of that layer alone."""
    x, w, tiles, grp = _ragged_case(rng, [9, 0, 20], 8, 32, 128)
    stack = jnp.stack([w * (i + 1) for i in range(3)]).astype(stack_dtype)
    rows = grp >= 0
    got = mp_dot_ragged(x, stack, tiles, policy="bf16", backend=backend,
                        layer=jnp.int32(2), out_dtype=jnp.float32)
    want = mp_dot_ragged(x, stack[2], tiles, policy="bf16",
                         backend=backend, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got)[rows],
                                  np.asarray(want)[rows])


@pytest.mark.parametrize("operand", ["packed", "sparse"])
def test_mp_dot_ragged_reads_packed_and_sparse_weights(rng, operand):
    """Pre-laid-out expert weights (``pack_params``/``sparsify_params``
    output) give the rows of the dense weights they stand for."""
    from repro.packing.pack import pack_operand
    from repro.sparse.sparsify import densify_operand, sparsify_magnitude
    x, w, tiles, grp = _ragged_case(rng, [9, 0, 20], 8, 32, 128)
    if operand == "packed":
        op = pack_operand(w, (16, 128), backend="interpret")
        dense = w
    else:
        op = sparsify_magnitude(w, (16, 128), density=0.5)
        dense = densify_operand(op)
    rows = grp >= 0
    for backend in ("xla", "interpret"):
        got = mp_dot_ragged(x, op, tiles, policy="bf16", backend=backend,
                            out_dtype=jnp.float32)
        want = mp_dot_ragged(x, dense, tiles, policy="bf16",
                             backend=backend, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got)[rows],
                                   np.asarray(want)[rows], atol=1e-6)
