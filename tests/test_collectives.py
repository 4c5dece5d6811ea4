"""distributed/collectives.py numerics: int8 grad compression against jnp
oracles on the host, and the shard_map collectives against plain sums on a
forced-host-device mesh (skipped below the needed device count — the CI
multidevice job runs with REPRO_FORCE_HOST_DEVICES=8)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.collectives import (
    compressed_psum, dequantize_grad, hierarchical_all_reduce,
    quantize_grad_int8,
)


def test_quantize_roundtrip_and_error_feedback(rng):
    g = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    err0 = jnp.zeros_like(g)
    q, scale, err = quantize_grad_int8(g, err0)
    assert q.dtype == jnp.int8
    deq = dequantize_grad(q, scale)
    # Quantization error bounded by half an int8 step, and the residual
    # carried forward is exactly that error (g + 0 - deq).
    step = float(scale)
    assert float(jnp.max(jnp.abs(deq - g))) <= 0.5 * step + 1e-7
    np.testing.assert_allclose(np.asarray(err), np.asarray(g - deq),
                               rtol=0, atol=1e-7)
    # Feeding the error back makes the SECOND step compensate: quantizing
    # the same gradient with the carried residual recovers g + err within
    # one step, so the two-step average error shrinks below step one's.
    q2, scale2, err2 = quantize_grad_int8(g, err)
    deq2 = dequantize_grad(q2, scale2)
    two_step_bias = float(jnp.max(jnp.abs((deq + deq2) / 2 - g)))
    assert two_step_bias <= 0.75 * step + 1e-7


def test_quantize_zero_grad_safe():
    g = jnp.zeros((4, 4), jnp.float32)
    q, scale, err = quantize_grad_int8(g, jnp.zeros_like(g))
    assert float(scale) > 0.0                     # clamped, no div-by-zero
    assert not np.asarray(q).any() and not np.asarray(err).any()


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices "
                           "(REPRO_FORCE_HOST_DEVICES=8)")
def test_compressed_psum_matches_sum_oracle(rng):
    p = 2
    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
    g = jnp.asarray(rng.standard_normal((p * 4, 8)), jnp.float32)
    err = jnp.zeros_like(g)

    @jax.jit
    def run(gg, ee):
        return jax.shard_map(
            lambda gl, el: compressed_psum(gl, el, "data"),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")), check_vma=False)(gg, ee)

    total, new_err = run(g, err)
    # Every shard's reduced value is the sum of ALL shards' dequantized
    # locals; tolerance is one int8 step per participating shard.
    want = np.asarray(g).reshape(p, 4, 8).sum(axis=0)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    got = np.asarray(total).reshape(p, 4, 8)
    for shard in got:
        np.testing.assert_allclose(shard, want, rtol=0,
                                   atol=p * scale + 1e-6)
    # Error feedback stays local: each shard's residual is bounded by its
    # own quantization step.
    assert float(jnp.max(jnp.abs(new_err))) <= 0.5 * scale + 1e-6


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >= 4 devices "
                           "(REPRO_FORCE_HOST_DEVICES=8)")
def test_hierarchical_all_reduce_matches_total_sum(rng):
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "data"))
    x = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)

    @jax.jit
    def run(xx):
        return jax.shard_map(
            lambda xl: hierarchical_all_reduce(xl),
            mesh=mesh, in_specs=(P("pod", "data"),),
            out_specs=P("pod", "data"), check_vma=False)(xx)

    got = np.asarray(run(x))
    # reduce-scatter in-pod + all-reduce cross-pod + all-gather in-pod ==
    # a plain all-reduce: every device block holds the total sum.
    # block (i, j) of the (pod, data)-sharded global is x[2i:2i+2, 3j:3j+3]
    # == reshape axes (pod, row, data, col); the total sums pod AND data.
    want = np.asarray(x).reshape(2, 2, 2, 3).sum(axis=(0, 2))
    for i in range(2):
        for j in range(2):
            np.testing.assert_allclose(got[2 * i:2 * i + 2, 3 * j:3 * j + 3],
                                       want, rtol=1e-6, atol=1e-6)
