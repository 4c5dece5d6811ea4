"""The float32 reference against the serving stack, at smoke size (CPU)."""
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.reference import Reference, gap_in_sd  # noqa: E402
from repro.configs import base as cb  # noqa: E402
from repro.models.transformer import build_model  # noqa: E402

CONFIGS = ["phi3-mini-3.8b", "granite-moe-1b-a400m"]


def smoke(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           f"{name}.json")) as f:
        conf = json.load(f)
    arch = cb.get(conf["arch"], smoke=True)
    return harness.smoke_config(conf, arch), arch


def _bf16_ulps(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ulp = np.abs(b) * 2.0 ** -7 + 1e-30
    return np.abs(a - b) / ulp


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_draws_the_served_weights(name):
    conf, arch = smoke(name)
    key = harness.seed_key(2**31 + 3)
    params = build_model(arch, policy=conf["policy"], remat=False).init(key)
    ref = Reference(conf, key)
    # The same draws; a fused multiply may round a rare element the other
    # way, by one bfloat16 step.
    def same(a, b):
        ulps = _bf16_ulps(a, b)
        return ulps.max() <= 1.0 and (ulps > 0).mean() < 1e-3

    assert same(params["embed"], ref.embed_table())
    if not conf["tie_word_embeddings"]:
        assert same(params["head"], ref.head())
    names = {"wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"),
             "wo": ("attn", "wo")}
    if arch.n_experts:
        names.update(router=("router",), wg=("w_gate",), wu=("w_up",),
                     wd=("w_down",))
    else:
        names.update(wg=("mlp", "w_gate"), wu=("mlp", "w_up"),
                     wd=("mlp", "w_down"))
    for layer in range(arch.n_layers):
        w = ref.layer_weights(ref.layer_keys[layer])
        for k, path in names.items():
            p = params["stack"][0]
            for part in path:
                p = p[part]
            assert same(p[layer], w[k]), (layer, k)


@pytest.mark.parametrize("name", CONFIGS)
def test_paged_prefill_then_decode_matches_reference(name):
    """Chunked prefill into the paged cache, then decode through it, in
    float32 on the reference's weights, agrees with the reference's full
    forward at every position that predicts a token."""
    conf, arch = smoke(name)
    key = harness.seed_key(17)
    ref = Reference(conf, key)
    model = build_model(arch, policy="fp32", remat=False)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        build_model(arch, policy=conf["policy"], remat=False).init(key))
    prompt, chunk, n_decode, ps = 32, 16, 8, 8
    seq = np.random.default_rng(0).integers(2, arch.vocab,
                                            (prompt + n_decode,))
    caches = model.init_paged_caches(1 + (prompt + n_decode) // ps + 1, ps)
    bt = jnp.arange(1, 1 + 8, dtype=jnp.int32)[None]
    step = jax.jit(model.paged_step)
    got = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, prompt, chunk):
            logits, caches = step(
                params, jnp.asarray(seq[None, start:start + chunk], jnp.int32),
                caches, bt, jnp.array([start], jnp.int32),
                jnp.array([chunk], jnp.int32))
        got.append(logits[0])
        for i in range(n_decode - 1):
            pos = prompt + i
            logits, caches = step(
                params, jnp.asarray(seq[None, pos:pos + 1], jnp.int32),
                caches, bt, jnp.array([pos], jnp.int32),
                jnp.array([1], jnp.int32))
            got.append(logits[0])
    got = np.asarray(jnp.stack(got))[:, :arch.vocab]
    want = np.asarray(ref.logits_at(
        seq[None, :prompt + n_decode - 1],
        np.arange(prompt - 1, prompt + n_decode - 1)[None]))[0]
    err = np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2))
    assert err < 1e-4, err
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_gap_in_sd():
    logits = jnp.array([[0.0, 1.0, 3.0, 2.0], [1.0, 1.0, 1.0, 5.0]])
    gaps = np.asarray(gap_in_sd(logits, jnp.array([2, 0], jnp.int32)))
    assert gaps[0] == 0.0
    assert gaps[1] == pytest.approx(4.0 / np.std([1.0, 1.0, 1.0, 5.0]))
