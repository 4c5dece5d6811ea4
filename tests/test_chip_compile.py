"""Main-path kernels compiled by the TPU compiler for a described v5e chip.

Interpret mode cannot see what Mosaic refuses (tile alignment, scoped VMEM,
unsupported vector ops).  These tests compile the serving path's kernels at
real widths (granite-moe-1b-a400m, phi3-mini-3.8b) for one chip of a
described ``v5e:2x2`` topology; nothing runs, no chip is needed.  The
topology is described inside a fixture, never at import, and every test
skips when the TPU compiler cannot describe it.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.blocking import plan_gemm, plan_with_blocks
from repro.kernels.flash_attention import paged_flash_attention
from repro.kernels.mpgemm import mpgemm_grouped_pallas, mpgemm_pallas
from repro.packing.pack import pack_operand

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip cannot be read back without one: keep
    them out of JAX's persistent compilation cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *args):
    """Compile ``fn`` for the described chip; -> the compiled HLO text."""
    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
    args = jax.tree_util.tree_map(place, args)
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("m,k,n,plan_blocks", [
    # decode: 4 rows, with a bk that leaves a K tail, so the predicated
    # K-edge runs on a block smaller than one sublane tile
    (4, 3072, 1024, (4, 512, 2048)),
    # prefill chunk: granite's attention projection, planner blocks
    (256, 1024, 512, None),
], ids=["decode", "prefill"])
def test_dense_projection(one_chip, m, k, n, plan_blocks):
    plan = (plan_with_blocks(m, n, k, *plan_blocks, "bfloat16", "bfloat16")
            if plan_blocks else None)
    text = _compile(one_chip,
                    lambda a, b: mpgemm_pallas(a, b, plan=plan,
                                               out_dtype=BF16),
                    _sds((m, k)), _sds((k, n)))
    assert "tpu_custom_call" in text


def test_grouped_experts_gated(one_chip):
    """granite's 32 experts, SwiGLU gate riding the grouped GEMM."""
    e, rows, d, f = 32, 256, 1024, 512
    text = _compile(
        one_chip,
        lambda a, b, g: mpgemm_grouped_pallas(
            a, b, activation="silu", gate=g, out_dtype=jnp.float32),
        _sds((e, rows, d)), _sds((e, d, f)), _sds((e, rows, f), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_packed_payload(one_chip, codec):
    """phi3-mini's MLP up projection over a packed payload, tiled by the
    planner as ``pack_params`` tiles it: the in-VMEM decode temporaries
    must fit the scoped VMEM limit."""
    m, k, n = 128, 3072, 8192
    plan = plan_gemm(m, n, k, "bfloat16", codec)
    packed = jax.eval_shape(
        lambda w: pack_operand(w, plan, dtype=codec, backend="xla"),
        _sds((k, n)))
    text = _compile(one_chip,
                    lambda a, p: mpgemm_pallas(a, p, out_dtype=BF16),
                    _sds((m, k)), packed)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tq", [1, 64], ids=["decode", "prefill"])
def test_paged_attention(one_chip, tq):
    """granite's 16 query / 8 KV heads of width 64 over 16-token pages,
    max_len 512 at batch 4."""
    b, h, hkv, d, ps, w = 4, 16, 8, 64, 16, 32
    pages = b * w + 1
    text = _compile(
        one_chip,
        lambda q, k, v, bt, qs, ln: paged_flash_attention(
            q, k, v, bt, qs, ln),
        _sds((b, h, tq, d)), _sds((pages, hkv, ps, d)),
        _sds((pages, hkv, ps, d)), _sds((b, w), jnp.int32),
        _sds((b,), jnp.int32), _sds((b,), jnp.int32))
    assert "tpu_custom_call" in text
