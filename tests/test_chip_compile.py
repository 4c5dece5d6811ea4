"""Main-path kernels compiled by the TPU compiler for a described v5e chip.

Interpret mode cannot see what Mosaic refuses (tile alignment, scoped VMEM,
unsupported vector ops).  These tests compile the serving path's kernels at
real widths (granite-moe-1b-a400m, phi3-mini-3.8b) for one chip of a
described ``v5e:2x2`` topology; nothing runs, no chip is needed.  The
topology is described inside a fixture, never at import, and every test
skips when the TPU compiler cannot describe it.
"""
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.blocking import plan_gemm, plan_with_blocks
from repro.kernels.flash_attention import kv_page_shape, paged_flash_attention
from repro.kernels.mpgemm import (
    RaggedTiles, mpgemm_grouped_pallas, mpgemm_pallas, mpgemm_ragged_pallas,
    ragged_block_rows, ragged_rows,
)
from repro.packing.pack import pack_operand

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.chip.metrics import (  # noqa: E402
    mpgemm_roofline, paged_attention_roofline,
)

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


@pytest.mark.parametrize("h,hkv,d,tq", [
    (16, 8, 64, 1), (16, 8, 64, 64), (32, 32, 96, 1), (32, 32, 96, 64),
    (32, 8, 120, 1),
], ids=["decode", "prefill", "phi3-decode", "phi3-prefill", "danube-decode"])
def test_paged_attention(one_chip, h, hkv, d, tq):
    """granite's 16 query / 8 KV heads of width 64, phi3-mini's 32 / 32 of
    width 96 and h2o-danube3's 32 / 8 of width 120, over 16-token pages,
    max_len 512 at batch 4: the page walk's DMAs slice whole lane rows out
    of the pool."""
    b, ps, w = 4, 16, 32
    pages = kv_page_shape(b * w + 1, hkv, ps, d)
    text = _compile(
        one_chip,
        lambda q, k, v, bt, qs, ln: paged_flash_attention(
            q, k, v, bt, qs, ln),
        _sds((b, h, tq, d)), _sds(pages), _sds(pages),
        _sds((b, w), jnp.int32), _sds((b,), jnp.int32),
        _sds((b,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [8, 512], ids=["decode", "mixed"])
def test_ragged_expert_launch(one_chip, tokens):
    """granite's three expert GEMMs over the expert-sorted buffer of a
    step of ``tokens`` tokens (top-8 of 32): bf16 rows into the up and
    the gated gate projection, f32 rows into the down projection."""
    e, d, f, k = 32, 1024, 512, 8
    bm = ragged_block_rows(tokens * k, e)
    r = ragged_rows(tokens * k, e)
    i32 = jnp.int32
    tiles = RaggedTiles(_sds((r // bm,), i32), _sds((r // bm,), i32),
                        _sds((1,), i32))
    for fn, ops in [
        (lambda x, w, t: mpgemm_ragged_pallas(x, w, t),
         (_sds((r, d)), _sds((e, d, f)))),
        (lambda x, w, t, g: mpgemm_ragged_pallas(
            x, w, t, activation="silu", gate=g),
         (_sds((r, d)), _sds((e, d, f)), _sds((r, f), jnp.float32))),
        (lambda x, w, t: mpgemm_ragged_pallas(x, w, t),
         (_sds((r, f), jnp.float32), _sds((e, f, d)))),
    ]:
        text = _compile(one_chip, lambda x, w, t, *g: fn(x, w, t, *g),
                        ops[0], ops[1], tiles, *ops[2:])
        assert "tpu_custom_call" in text


def _hlo_with_operand_shapes(compiled) -> str:
    """Compiled HLO printed as the profiler names its ops: every operand
    with its shape, so a custom call's first operand type is visible."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    return "\n".join(m.to_string(opts) for m in
                     compiled.runtime_executable().hlo_modules())


@pytest.mark.parametrize("chunk", [1, 64], ids=["decode", "mixed"])
def test_paged_step_kernel_classes(one_chip, chunk):
    """granite's whole served step: the benchmark's kernel classes, told
    apart by a custom call's first operand, count every GEMM launch
    (dense and ragged expert) as ``mpgemm`` and only the attention kernel
    as ``paged_attention``.  The ragged launches read the experts of the
    whole (24, 32, ., .) weight stacks, so no per-layer copy of them is
    made outside the GEMM kernels."""
    from repro.configs import base as cb
    from repro.core import config as gemm_cfg
    from repro.models.transformer import build_model
    model = build_model(cb.get("granite-moe-1b-a400m"), policy="bf16_serve",
                        remat=False)
    b, w = 8, 8
    args = (jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            _sds((b, chunk), jnp.int32),
            jax.eval_shape(lambda: model.init_paged_caches(b * w + 1, 16)),
            _sds((b, w), jnp.int32), _sds((b,), jnp.int32),
            _sds((b,), jnp.int32))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    with gemm_cfg.gemm_backend("pallas"):
        compiled = jax.jit(model.paged_step).lower(*args).compile()
    gemm = re.compile(mpgemm_roofline.KERNELS["mpgemm"])
    attention = re.compile(paged_attention_roofline.KERNELS["paged_attention"])
    calls = [line.strip() for line in
             _hlo_with_operand_shapes(compiled).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.match(r"(?:ROOT )?%?(\S+) = ", c).group(1) for c in calls]
    ragged = [c for n, c in zip(names, calls) if n.startswith("mpgemm_ragged")]
    assert len(ragged) == 3
    for call in ragged:
        assert re.search(r"custom-call\(\S+ %[^,]+, bf16\[24,32,\d+,\d+\]",
                         call)
    assert not re.search(r"= bf16\[32,(512,1024|1024,512)\]",
                         _hlo_with_operand_shapes(compiled))
    assert sum(n.startswith("paged_flash_attention") for n in names) == 1
    for name, call in zip(names, calls):
        is_attention = name.startswith("paged_flash_attention")
        assert is_attention or name.startswith("mpgemm"), name
        assert bool(attention.search(call)) == is_attention, name
        assert bool(gemm.search(call)) != is_attention, name
