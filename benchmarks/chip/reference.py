"""Plain float32 reference forward of the benchmark's decoder LMs.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching tricks.  It reads the model's sizes from the
configuration file (Hugging Face key names) and nothing from the serving
program.  Weights are drawn here, layer by layer, from the run's seed with
the recipe the served model is initialised by (below); each is rounded to
bfloat16, the precision the configuration serves in, and computed with in
float32.  A layer's weights exist only while that layer runs, so the
forward fits next to nothing else on the chip.

Architecture (pre-norm decoder): token embedding (times
``embedding_multiplier``); per layer RMSNorm (scale 1 + w), causal
self-attention with rotary embeddings on the whole head (halves rotated),
grouped KV heads and score scale ``attention_multiplier``, residual add
(times ``residual_multiplier``), RMSNorm, then a SwiGLU MLP
(silu(x Wg) * (x Wu) Wd) or, with ``num_local_experts``, a router softmax
whose top ``num_experts_per_tok`` experts (weights renormalised to sum to
1) each apply a SwiGLU MLP; a final RMSNorm and the head (the embedding
table transposed when tied), divided by ``logits_scaling``.

Weight recipe, from ``key = PRNGKey(seed)``: ``keys = split(key, 8)``;
embedding ``N(0, 1) * 0.02`` from ``keys[0]`` over the vocabulary padded
to a multiple of 128; an untied head ``N(0, 1) * 0.02`` from ``keys[1]``;
layer i from ``split(fold_in(keys[3], 0), layers)[i]``.  A dense layer
splits its key in two (attention, MLP); a mixture-of-experts layer in five
(attention, router, gate, up, down experts).  Attention splits its key in
four for Wq, Wk, Wv, Wo; the MLP in three for Wg, Wu, Wd.  A matrix of
shape (a, b) is ``N(0, 1) * sqrt(2 / (a + b))``; the expert stacks (E, a,
b) use ``sqrt(2 / (d + f))`` for all three.  Norm weights are zero.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 128) * 128


def _bf16(x):
    """A weight as the configuration stores it, computed with in f32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, scale):
    return _bf16(jax.random.normal(key, shape) * scale)


def _dense(key, a, b):
    return _normal(key, (a, b), (2.0 / (a + b)) ** 0.5)


def _int8(x):
    """Symmetric per-tensor int8 rounding of a matmul operand."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


class Reference:
    """The reference model of one configuration under one weight key.

    ``int8=True`` rounds both operands of every weight product (the
    projections, the MLP or experts, and the head; not the router and not
    attention) to int8 per tensor: the precision step below the bfloat16
    the configurations state, used as the control of the correctness
    check."""

    def __init__(self, config: dict, key, int8: bool = False):
        self.int8 = int8
        c = config
        self.d = c["hidden_size"]
        self.heads = c["num_attention_heads"]
        self.kv_heads = c["num_key_value_heads"]
        self.head_dim = c.get("head_dim") or self.d // self.heads
        self.ff = c["intermediate_size"]
        self.layers = c["num_hidden_layers"]
        self.vocab = c["vocab_size"]
        self.experts = c.get("num_local_experts", 0)
        self.top_k = c.get("num_experts_per_tok", 0)
        self.theta = float(c["rope_theta"])
        self.eps = float(c["rms_norm_eps"])
        self.tied = bool(c["tie_word_embeddings"])
        self.window = c.get("sliding_window")
        self.attn_scale = float(c.get("attention_multiplier")
                                or 1.0 / math.sqrt(self.head_dim))
        self.emb_mult = float(c.get("embedding_multiplier", 1.0))
        self.res_mult = float(c.get("residual_multiplier", 1.0))
        self.logit_div = float(c.get("logits_scaling", 1.0))
        keys = jax.random.split(key, 8)
        self.k_embed, self.k_head = keys[0], keys[1]
        self.layer_keys = jax.random.split(jax.random.fold_in(keys[3], 0),
                                           self.layers)

    # ------------------------------------------------------------ weights

    def embed_table(self):
        return _normal(self.k_embed, (padded_vocab(self.vocab), self.d), 0.02)

    def head(self):
        if self.tied:
            return self.embed_table().T
        return _normal(self.k_head, (self.d, padded_vocab(self.vocab)), 0.02)

    def layer_weights(self, key) -> dict:
        d, hd, f = self.d, self.head_dim, self.ff
        if self.experts:
            k_attn, k_router, k_gate, k_up, k_down = jax.random.split(key, 5)
        else:
            k_attn, k_mlp = jax.random.split(key)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        w = {"wq": _dense(kq, d, self.heads * hd),
             "wk": _dense(kk, d, self.kv_heads * hd),
             "wv": _dense(kv, d, self.kv_heads * hd),
             "wo": _dense(ko, self.heads * hd, d),
             "ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,))}
        if self.experts:
            e, s = self.experts, (2.0 / (d + f)) ** 0.5
            w["router"] = _dense(k_router, d, e)
            w["wg"] = _normal(k_gate, (e, d, f), s)
            w["wu"] = _normal(k_up, (e, d, f), s)
            w["wd"] = _normal(k_down, (e, f, d), s)
        else:
            kg, ku, kd = jax.random.split(k_mlp, 3)
            w["wg"], w["wu"], w["wd"] = (_dense(kg, d, f), _dense(ku, d, f),
                                         _dense(kd, f, d))
        return w

    # ------------------------------------------------------------ layers

    def _mm(self, spec, x, w):
        if self.int8:
            x, w = _int8(x), _int8(w)
        return jnp.einsum(spec, x, w, precision=HIGHEST)

    def _rms(self, x, w):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps)
        return x * (1.0 + w)

    def _rope(self, x):
        """x: (B, H, T, hd) at positions 0..T-1."""
        hd, t = self.head_dim, x.shape[2]
        inv = 1.0 / (self.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                    / hd))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def _attention(self, h, w):
        b, t, _ = h.shape
        hd, g = self.head_dim, self.heads // self.kv_heads

        def heads(x, n):
            return x.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

        q = self._rope(heads(self._mm("btd,df->btf", h, w["wq"]), self.heads))
        k = self._rope(heads(self._mm("btd,df->btf", h, w["wk"]),
                             self.kv_heads))
        v = heads(self._mm("btd,df->btf", h, w["wv"]), self.kv_heads)
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision=HIGHEST) * self.attn_scale
        qi = jnp.arange(t)[:, None]
        ki = jnp.arange(t)[None, :]
        mask = ki <= qi
        if self.window:
            mask &= ki > qi - self.window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HIGHEST)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, self.heads * hd)
        return self._mm("btf,fd->btd", o, w["wo"])

    def _swiglu(self, h, wg, wu, wd):
        g = jax.nn.silu(self._mm("btd,df->btf", h, wg))
        return self._mm("btf,fd->btd", g * self._mm("btd,df->btf", h, wu), wd)

    def _moe(self, h, w):
        probs = jax.nn.softmax(jnp.dot(h, w["router"], precision=HIGHEST),
                               axis=-1)
        top_w, top_i = jax.lax.top_k(probs, self.top_k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        # gate[..., e]: the weight expert e gets (0 where not chosen).
        gate = jnp.sum(jax.nn.one_hot(top_i, self.experts) * top_w[..., None],
                       axis=-2)
        hg = self._mm("btd,edf->ebtf", h, w["wg"])
        hu = self._mm("btd,edf->ebtf", h, w["wu"])
        y = self._mm("ebtf,efd->ebtd", jax.nn.silu(hg) * hu, w["wd"])
        return jnp.einsum("ebtd,bte->btd", y, gate, precision=HIGHEST)

    def _layer(self, x, key):
        w = self.layer_weights(key)
        x = x + self.res_mult * self._attention(self._rms(x, w["ln1"]), w)
        h = self._rms(x, w["ln2"])
        mlp = (self._moe(h, w) if self.experts
               else self._swiglu(h, w["wg"], w["wu"], w["wd"]))
        return x + self.res_mult * mlp

    @functools.cached_property
    def _layer_fn(self):
        return jax.jit(self._layer)

    @functools.cached_property
    def _embed_fn(self):
        return jax.jit(lambda tok: self.embed_table()[tok] * self.emb_mult)

    @functools.cached_property
    def _logits_fn(self):
        def f(x, pos):
            x = jnp.take_along_axis(x, pos[..., None], axis=1)
            x = self._rms(x, jnp.zeros((self.d,)))
            logits = self._mm("bsd,dv->bsv", x, self.head())
            return logits[..., :self.vocab] / self.logit_div
        return jax.jit(f)

    def logits_at(self, tokens: np.ndarray, positions: np.ndarray):
        """Logits (B, S, vocab) at ``positions`` (B, S) of ``tokens`` (B, T),
        each position predicting the token after it."""
        x = self._embed_fn(jnp.asarray(tokens, jnp.int32))
        for i in range(self.layers):
            x = self._layer_fn(x, self.layer_keys[i])
        return self._logits_fn(x, jnp.asarray(positions, jnp.int32))


@jax.jit
def gap_in_sd(logits, chosen):
    """How far each chosen token's logit lies below the best logit of its
    position, in standard deviations of that position's logits.

    logits: (..., V) float32; chosen: (...) int32.  -> (...) float32."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return (best - got) / jnp.std(logits, axis=-1)
