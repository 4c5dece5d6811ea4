"""MoE dispatch: gather-based routing must equal the dense reference when
capacity is ample; capacity drops degrade gracefully; aux losses sane; the
served step's ragged dispatch is dropless and equals the capacity path
where that one drops nothing."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import base as cb
from repro.core import config as gemm_cfg
from repro.models.blocks import init_moe, moe_mlp, moe_mlp_ragged


def _dense_moe_ref(params, x, cfg):
    """Reference: every expert computes every token; combine by top-k gate."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = (x.astype(jnp.float32).reshape(-1, d)
              @ params["router"].astype(jnp.float32)).reshape(b, s, e)
    gates = jax.nn.softmax(logits, -1)
    topw, topi = jax.lax.top_k(gates, k)
    topw = topw / topw.sum(-1, keepdims=True)
    xc = x.astype(jnp.bfloat16).reshape(-1, d)
    ys = []
    for ei in range(e):   # per-expert 2-D dots (CPU thunk compatible)
        hg = jax.lax.dot(xc, params["w_gate"][ei].astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        hu = jax.lax.dot(xc, params["w_up"][ei].astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        hh = (jax.nn.silu(hg) * hu).astype(jnp.bfloat16)
        ys.append(jax.lax.dot(hh, params["w_down"][ei].astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32))
    y = jnp.stack(ys, 0).reshape(e, b, s, d)                 # (e,b,s,d)
    sel = jnp.stack([jnp.take_along_axis(
        y.transpose(1, 2, 0, 3), topi[..., j:j + 1, None], axis=2)[:, :, 0]
        for j in range(k)], axis=2)                          # (b,s,k,d)
    out = jnp.einsum("bskd,bsk->bsd", sel.astype(jnp.float32),
                     topw.astype(jnp.float32))
    return out


def test_moe_matches_dense_reference(rng):
    cfg = cb.get("granite-moe-1b-a400m", smoke=True)
    params = init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.standard_normal((2, 32, cfg.d_model)), "float32") \
        .astype(jnp.bfloat16)
    out, aux = moe_mlp(params, x, cfg, "bf16", capacity_factor=8.0)
    ref = _dense_moe_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
    assert jnp.isfinite(aux) and float(aux) > 0


def test_moe_capacity_drops_are_partial(rng):
    """With tiny capacity, output degrades but stays finite and nonzero."""
    cfg = cb.get("mixtral-8x22b", smoke=True)
    params = init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.standard_normal((1, 64, cfg.d_model)), "float32") \
        .astype(jnp.bfloat16)
    full, _ = moe_mlp(params, x, cfg, "bf16", capacity_factor=8.0)
    tight, _ = moe_mlp(params, x, cfg, "bf16", capacity_factor=0.25)
    assert bool(jnp.all(jnp.isfinite(tight)))
    # some tokens dropped -> outputs differ
    assert float(jnp.max(jnp.abs(full - tight))) > 0


def test_moe_grads_flow_to_all_parts(rng):
    cfg = cb.get("granite-moe-1b-a400m", smoke=True)
    params = init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.standard_normal((1, 32, cfg.d_model)), "float32") \
        .astype(jnp.bfloat16)

    def loss(p):
        out, aux = moe_mlp(p, x, cfg, "bf16")
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    g = jax.grad(loss)(params)
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert float(jnp.abs(g[name]).sum()) > 0, f"no grad to {name}"


def _moe_cfg(experts, top_k):
    return dataclasses.replace(cb.get("granite-moe-1b-a400m", smoke=True),
                               n_experts=experts, experts_per_token=top_k)


def _live(n_valid, c):
    return jnp.arange(c)[None, :] < jnp.asarray(n_valid)[:, None]


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_ragged_dispatch_equals_dropless_capacity_path(rng, backend):
    """At granite's top_k / n_experts = 1/4, capacity 4.0 holds every
    token: the ragged path equals it on live tokens (full, partial and
    idle rows) and gives dead tokens zeros."""
    cfg = _moe_cfg(16, 4)
    params = init_moe(jax.random.PRNGKey(1), cfg)
    b, c = 4, 16
    x = jnp.asarray(rng.standard_normal((b, c, cfg.d_model)),
                    "float32").astype(jnp.bfloat16)
    live = _live([16, 0, 5, 1], c)
    cap, _ = moe_mlp(params, x, cfg, "bf16", capacity_factor=4.0)
    with gemm_cfg.gemm_backend(backend):
        rag = moe_mlp_ragged(params, x, live, cfg, "bf16")
    got = np.asarray(rag, np.float32)
    want = np.asarray(cap, np.float32)
    m = np.asarray(live)
    np.testing.assert_allclose(got[m], want[m], atol=2e-2, rtol=2e-2)
    assert np.abs(got[~m]).max() == 0.0


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_ragged_dispatch_is_dropless_where_capacity_drops(rng, backend):
    """top_k / n_experts = 1/16 with every token routed to one expert:
    capacity 4.0 keeps a quarter of them, the ragged path matches the
    dense reference on every live token."""
    cfg = _moe_cfg(16, 1)
    params = init_moe(jax.random.PRNGKey(1), cfg)
    base = rng.standard_normal((1, 1, cfg.d_model))
    x = jnp.asarray(base + 0.01 * rng.standard_normal((2, 32, cfg.d_model)),
                    "float32").astype(jnp.bfloat16)
    live = np.asarray(_live([32, 20], 32))
    assert len(np.unique(np.asarray(jax.lax.top_k(
        x.astype(jnp.float32) @ params["router"], 1)[1]))) == 1
    ref = np.asarray(_dense_moe_ref(params, x, cfg), np.float32)
    cap, _ = moe_mlp(params, x, cfg, "bf16", capacity_factor=4.0)
    with gemm_cfg.gemm_backend(backend):
        rag = moe_mlp_ragged(params, x, jnp.asarray(live), cfg, "bf16")
    np.testing.assert_allclose(np.asarray(rag, np.float32)[live],
                               ref[live], atol=3e-2)
    assert np.abs(np.asarray(cap, np.float32)[live] - ref[live]).max() > 0.1


def _pallas_calls(jaxpr, name):
    """Every pallas_call eqn called ``name`` in ``jaxpr`` and its
    sub-jaxprs (the layer scan's body)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and name in str(
                eqn.params.get("name", "")):
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_calls(sub, name)
    return out


def test_paged_step_reads_expert_stacks_in_place(rng):
    """The served step hands the ragged expert launches each layer's
    expert weights as the whole (L, E, ., .) stack (no per-layer slice);
    its logits for a prompt equal the capacity-path prefill's, on the
    kernel and on the XLA backend, with an idle row beside it."""
    from repro.models.transformer import build_model
    cfg = cb.get("granite-moe-1b-a400m", smoke=True)
    model = build_model(cfg, policy="bf16_serve", remat=False)
    params = model.init(jax.random.PRNGKey(0))
    n, ps, w = 8, 4, 4
    prompt = jnp.asarray(rng.integers(2, cfg.vocab, (1, n)), jnp.int32)
    want, _ = model.prefill(params, {"tokens": prompt})
    caches = model.init_paged_caches(2 * w + 1, ps)
    args = (params, jnp.concatenate([prompt, jnp.zeros_like(prompt)]),
            caches, jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.asarray([n, 0], jnp.int32))
    with gemm_cfg.gemm_backend("interpret"):
        calls = _pallas_calls(jax.make_jaxpr(model.paged_step)(*args).jaxpr,
                              "mpgemm_ragged")
        assert len(calls) == 3
        for eqn in calls:
            assert eqn.invars[1].aval.shape[:2] == (cfg.n_layers,
                                                    cfg.n_experts)
    for backend in ("interpret", "xla"):
        with gemm_cfg.gemm_backend(backend):
            got, _ = jax.jit(model.paged_step)(*args)
        np.testing.assert_allclose(np.asarray(got[0], np.float32),
                                   np.asarray(want[0], np.float32),
                                   atol=2e-2, rtol=0)
