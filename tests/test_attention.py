"""Attention variants vs dense reference + flash Pallas kernel sweeps."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref
from repro.models.attention import (
    banded_window_attention, chunked_attention, decode_attention,
    dense_attention,
)


def _qkv(rng, b, h, hkv, tq, tk, d, dtype="float32"):
    q = jnp.asarray(rng.standard_normal((b, h, tq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, tk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, tk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("b,h,hkv,tq,tk,d,causal,window", [
    (2, 4, 2, 256, 256, 64, True, None),
    (1, 4, 1, 200, 200, 64, True, None),       # irregular T
    (2, 8, 2, 128, 384, 64, True, None),       # right-aligned continuation
    (1, 2, 2, 256, 256, 64, True, 96),         # sliding window
    (1, 4, 4, 160, 160, 128, False, None),     # cross-attention style
    (1, 2, 1, 1, 300, 64, True, None),         # single-token decode
])
def test_flash_kernel_vs_oracle(rng, b, h, hkv, tq, tk, d, causal, window):
    q, k, v = _qkv(rng, b, h, hkv, tq, tk, d)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=128, interpret=True)
    kr = jnp.repeat(k, h // hkv, axis=1)
    vr = jnp.repeat(v, h // hkv, axis=1)
    ref = flash_attention_ref(q, kr, vr, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_flash_kernel_dtypes(rng, dtype, atol):
    q, k, v = _qkv(rng, 1, 4, 2, 192, 192, 64, dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    kr = jnp.repeat(k, 2, axis=1)
    vr = jnp.repeat(v, 2, axis=1)
    ref = flash_attention_ref(q, kr, vr)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_chunked_matches_dense(rng):
    q, k, v = _qkv(rng, 2, 4, 2, 300, 300, 64)
    ref = dense_attention(q, k, v, causal=True)
    out = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=96)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_chunked_grads_match_dense(rng):
    q, k, v = _qkv(rng, 1, 2, 2, 128, 128, 32)
    g1 = jax.grad(lambda q: jnp.sum(
        chunked_attention(q, k, v, q_chunk=32, kv_chunk=32) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(dense_attention(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-4)


def test_banded_matches_dense_window(rng):
    q, k, v = _qkv(rng, 2, 4, 2, 300, 300, 64)
    w = 64
    ref = dense_attention(q, k, v, causal=True, window=w)
    out = banded_window_attention(q, k, v, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_banded_flops_subquadratic():
    """The banded path's HLO must NOT contain a T x T logits tensor."""
    t, w = 4096, 256
    q = jnp.zeros((1, 2, t, 64))
    txt = jax.jit(lambda q: banded_window_attention(q, q, q, window=w)) \
        .lower(q).as_text()
    assert f"{t},{t}" not in txt  # no quadratic intermediate


def test_decode_matches_dense(rng):
    q, k, v = _qkv(rng, 2, 4, 2, 1, 300, 64)
    lengths = jnp.array([200, 300])
    out = decode_attention(q, k, v, lengths)
    for i, L in enumerate([200, 300]):
        ref = dense_attention(q[i:i + 1], k[i:i + 1, :, :L], v[i:i + 1, :, :L],
                              causal=False)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   atol=1e-5)


# --- ops.py wrapper: explicit, logged-once ref fallback ----------------------

def _traced_pallas_call(fn, *args, **kwargs):
    """Does tracing fn(*args, **kwargs) reach a pallas_call primitive?"""
    import functools as _ft
    jaxpr = jax.make_jaxpr(_ft.partial(fn, **kwargs))(*args)

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                return True
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if walk(sub):
                    return True
        return False

    return walk(jaxpr.jaxpr)


def test_ops_flash_attention_path_traced(rng, caplog):
    import logging

    from repro.kernels import ops

    q, k, v = _qkv(rng, 1, 4, 2, 16, 16, 64)

    # Kernel path: the traced computation contains the pallas_call.
    assert _traced_pallas_call(ops.flash_attention, q, k, v, interpret=True)
    # Explicit XLA request: reference path, and NOT an implicit fallback.
    ops._FALLBACKS_LOGGED.discard("flash_attention")
    with caplog.at_level(logging.WARNING, logger="repro.kernels.ops"):
        assert not _traced_pallas_call(ops.flash_attention, q, k, v,
                                       backend="xla")
    assert not caplog.records

    # Implicit fallback (non-float operands): reference path, logged ONCE.
    qi = jnp.zeros(q.shape, jnp.int32)
    ki = jnp.zeros(k.shape, jnp.int32)
    vi = jnp.zeros(v.shape, jnp.int32)
    reason = ops.flash_attention_fallback_reason(
        qi.dtype, ki.dtype, vi.dtype, backend="pallas")
    assert reason is not None and "non-float" in reason
    with caplog.at_level(logging.WARNING, logger="repro.kernels.ops"):
        assert not _traced_pallas_call(ops.flash_attention, qi, ki, vi,
                                       interpret=True)
        assert not _traced_pallas_call(ops.flash_attention, qi, ki, vi,
                                       interpret=True, causal=False)
    fallback_logs = [r for r in caplog.records if "reference path" in r.message]
    assert len(fallback_logs) == 1  # logged once, later fallbacks silent


def test_ops_flash_attention_compiled_path_refuses_fallback(rng):
    """On the compiled (TPU) path an implicit reference fallback would hide
    the device behind XLA: it is an error, not a log line."""
    from repro.kernels import ops

    q, k, v = _qkv(rng, 1, 4, 2, 16, 16, 64)
    qi = jnp.zeros(q.shape, jnp.int32)
    with pytest.raises(ValueError, match="backend='xla'"):
        ops.flash_attention(qi, k, v)
    # The explicit reference route stays open.
    assert not _traced_pallas_call(ops.flash_attention, qi, k, v,
                                   backend="xla")


def test_ops_flash_attention_fallback_matches_kernel(rng):
    from repro.kernels import ops

    q, k, v = _qkv(rng, 1, 4, 2, 16, 16, 64)
    y_kernel = ops.flash_attention(q, k, v, interpret=True)
    y_ref = ops.flash_attention(q, k, v, backend="xla")
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)


def test_ops_flash_attention_gqa_mismatch_raises(rng):
    from repro.kernels import ops

    q, k, v = _qkv(rng, 1, 4, 2, 16, 16, 64)
    with pytest.raises(ValueError, match="GQA requires"):
        ops.flash_attention(q[:, :3], k, v, interpret=True)
