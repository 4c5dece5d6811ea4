"""Per-layer blocks with a uniform interface used by the stack machinery in
``models/transformer.py``:

  init_<kind>(key, cfg)                         -> params
  <kind>_fwd(params, x, ctx)                    -> (x, aux, cache|None)
  <kind>_decode(params, x, cache, ctx)          -> (x, cache)
  <kind>_init_cache(cfg, batch, max_len, dtype) -> cache (static shapes)

``ctx`` keys: cfg, policy, backend, rope=(cos,sin)|None, positions, causal,
collect_cache (bool), cache_len (int), pos (decode-time scalar),
cross_states (B,Tsrc,d) for cross/enc-dec kinds.

``aux`` is a scalar f32 auxiliary loss contribution (MoE load-balance +
router z-loss; 0 elsewhere).  Every matmul routes through mp_dot — the
paper's GEMM technique is the substrate of every block.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import config as gemm_cfg
from repro.core.gemm import mp_dot, mp_dot_grouped, mp_dot_ragged
from repro.distributed import act
from repro.kernels.flash_attention import kv_page_shape
from repro.kernels.mpgemm import (
    ragged_block_rows, ragged_layout, ragged_rows,
)
from repro.models import attention as attn
from repro.models.layers import (
    apply_rope, dense_init, gelu_mlp, init_gelu_mlp, init_swiglu, layernorm,
    rmsnorm, swiglu_mlp,
)

ZERO = jnp.float32(0.0)


def norm(params, x, cfg):
    if cfg.norm == "layer":
        return layernorm(x, params["scale"], params["bias"])
    return rmsnorm(x, params["scale"])


def init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layer":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.zeros((d,), jnp.float32)}


def _mlp(params, x, cfg, policy, residual=None):
    """MLP with the block residual riding the down-projection's epilogue
    (``residual=`` — models/layers.py); callers pass the pre-norm stream."""
    if cfg.mlp == "gelu":
        return gelu_mlp(params, x, policy, residual=residual)
    return swiglu_mlp(params, x, policy, residual=residual)


def _init_mlp(key, cfg):
    if cfg.mlp == "gelu":
        return init_gelu_mlp(key, cfg.d_model, cfg.d_ff, bias=cfg.mlp_bias)
    return init_swiglu(key, cfg.d_model, cfg.d_ff)


# --- attention plumbing --------------------------------------------------------

def init_attn(key, cfg, d_kv: Optional[int] = None):
    d, hd = cfg.d_model, cfg.head_dim
    d_kv = d_kv or d
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d, cfg.n_heads * hd),
        "wk": dense_init(k2, d_kv, cfg.n_kv_heads * hd),
        "wv": dense_init(k3, d_kv, cfg.n_kv_heads * hd),
        "wo": dense_init(k4, cfg.n_heads * hd, d),
    }


def _split_heads(x, n_heads, hd):
    b, t, _ = x.shape
    return x.reshape(b, t, n_heads, hd).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, t, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * hd)


def attn_qkv(params, x, cfg, ctx, kv_source=None):
    policy = ctx["policy"]
    hd = cfg.head_dim
    q = _split_heads(mp_dot(x, params["wq"], policy=policy), cfg.n_heads, hd)
    src = kv_source if kv_source is not None else x
    k = _split_heads(mp_dot(src, params["wk"], policy=policy), cfg.n_kv_heads, hd)
    v = _split_heads(mp_dot(src, params["wv"], policy=policy), cfg.n_kv_heads, hd)
    return q, k, v


def _self_attention(params, h, ctx, window):
    """Normed input -> attention output (+ optional (k, v) for caching)."""
    cfg = ctx["cfg"]
    q, k, v = attn_qkv(params, h, cfg, ctx)
    if ctx.get("rope") is not None:
        cos, sin = ctx["rope"]
        q = apply_rope(q, cos, sin, ctx.get("positions"))
        k = apply_rope(k, cos, sin, ctx.get("positions"))
    o = attn.attention_core(
        q, k, v, causal=ctx.get("causal", True), window=window,
        backend=ctx.get("backend"),
    )
    out = mp_dot(_merge_heads(o), params["wo"], policy=ctx["policy"])
    kv = (k, v) if ctx.get("collect_cache") else None
    return out, kv


def _kv_to_ring_cache(kv, cache_len: int, dtype):
    """Pack prefill K/V (B,Hkv,S,hd) into a ring cache of size cache_len.

    Position p lands in slot p % cache_len, matching decode's ring write."""
    k, v = kv
    s = k.shape[2]
    if s <= cache_len:
        pad = [(0, 0), (0, 0), (0, cache_len - s), (0, 0)]
        return {"k": jnp.pad(k, pad).astype(dtype),
                "v": jnp.pad(v, pad).astype(dtype)}
    k_tail = k[:, :, s - cache_len:]
    v_tail = v[:, :, s - cache_len:]
    slots = (jnp.arange(cache_len) + (s - cache_len)) % cache_len
    zk = jnp.zeros(k_tail.shape, dtype)
    return {"k": zk.at[:, :, slots].set(k_tail.astype(dtype)),
            "v": zk.at[:, :, slots].set(v_tail.astype(dtype))}


def attn_init_cache(cfg, batch, max_len, dtype=jnp.bfloat16, window=None):
    hd = cfg.head_dim
    cache_len = min(window, max_len) if window else max_len
    shape = (batch, cfg.n_kv_heads, cache_len, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(params, x, cache, ctx):
    """x: (B,1,d) normed input -> (attn output, updated ring cache)."""
    cfg = ctx["cfg"]
    pos = ctx["pos"]
    q, k, v = attn_qkv(params, x, cfg, ctx)
    if ctx.get("rope") is not None:
        cos, sin = ctx["rope"]
        if ctx.get("rope_single_row"):
            pidx = jnp.zeros((x.shape[0], 1), jnp.int32)  # row 0 = current pos
        else:
            pidx = jnp.full((x.shape[0], 1), pos, jnp.int32)
        q = apply_rope(q, cos, sin, pidx)
        k = apply_rope(k, cos, sin, pidx)
    mesh = act.current_mesh()
    if mesh is not None and attn.can_flash_decode(q, cache["k"], mesh):
        # Sequence-parallel flash decode: cond-guarded local ring write +
        # LSE psum combine.
        o, kc, vc = attn.flash_decode_sharded(
            q, cache["k"], cache["v"], k, v, pos, mesh)
    else:
        s_max = cache["k"].shape[2]
        slot = pos % s_max
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
        lengths = jnp.minimum(pos + 1, s_max) * jnp.ones(
            (x.shape[0],), jnp.int32)
        o = attn.decode_attention(q, kc, vc, lengths)
    out = mp_dot(_merge_heads(o), params["wo"], policy=ctx["policy"])
    return out, {"k": kc, "v": vc}


def attn_paged_init_cache(cfg, num_pages, page_size, dtype=jnp.bfloat16):
    """Pooled KV pages shared by every request (serve/kv_cache.py owns the
    allocation of the leading page axis; page 0 is reserved scratch), in
    the paged kernel's layout: KV heads side by side in each page row."""
    shape = kv_page_shape(num_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def attn_paged_step(params, x, cache, ctx, window=None):
    """x: (B, C, d) normed chunk -> (attn output, updated page pool).

    One code path serves both decode (C == 1) and chunked prefill (C ==
    chunk): rows past ctx["paged"]["n_valid"][b] are dead padding, routed
    to the scratch page on write and masked out of the softmax by the
    logical-position bounds."""
    cfg = ctx["cfg"]
    pg = ctx["paged"]
    q, k, v = attn_qkv(params, x, cfg, ctx)
    if ctx.get("rope") is not None:
        cos, sin = ctx["rope"]
        q = apply_rope(q, cos, sin, ctx["positions"])
        k = apply_rope(k, cos, sin, ctx["positions"])
    kc, vc = attn.paged_kv_write(
        cache["k_pages"], cache["v_pages"], k, v,
        pg["block_tables"], pg["q_start"], pg["n_valid"])
    o = attn.paged_attention(
        q, kc, vc, pg["block_tables"], pg["q_start"], pg["lengths"],
        window=window, backend=ctx.get("backend"))
    out = mp_dot(_merge_heads(o), params["wo"], policy=ctx["policy"])
    return out, {"k_pages": kc, "v_pages": vc}


# =============================== dense =========================================

def init_dense(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": init_norm(cfg), "attn": init_attn(k1, cfg),
            "ln2": init_norm(cfg), "mlp": _init_mlp(k2, cfg)}


def _dense_window(cfg, kind):
    return cfg.local_attn_window if kind == "attn_local" else cfg.window


def dense_fwd(params, x, ctx, *, window=None):
    cfg = ctx["cfg"]
    o, kv = _self_attention(params["attn"], norm(params["ln1"], x, cfg), ctx, window)
    x = x + o
    x = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"],
             residual=x)
    cache = None
    if kv is not None:
        cache = _kv_to_ring_cache(kv, ctx["cache_len"] if window is None
                                  else min(window, ctx["cache_len"]),
                                  ctx.get("cache_dtype", jnp.bfloat16))
    return x, ZERO, cache


def dense_decode(params, x, cache, ctx):
    cfg = ctx["cfg"]
    o, cache = attn_decode(params["attn"], norm(params["ln1"], x, cfg), cache, ctx)
    x = x + o
    x = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"],
             residual=x)
    return x, cache


def dense_paged_step(params, x, cache, ctx, *, window=None):
    cfg = ctx["cfg"]
    o, cache = attn_paged_step(params["attn"], norm(params["ln1"], x, cfg),
                               cache, ctx, window=window)
    x = x + o
    x = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"],
             residual=x)
    return x, cache


def dense_init_cache(cfg, batch, max_len, dtype=jnp.bfloat16, window=None):
    return attn_init_cache(cfg, batch, max_len, dtype, window=window)


# =============================== cross (VLM) ===================================

def init_cross(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_norm(cfg), "xattn": init_attn(k1, cfg),
        "ln2": init_norm(cfg), "mlp": _init_mlp(k2, cfg),
        "gate_attn": jnp.zeros((), jnp.float32),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


def _cross_attention(params, h, ctx, kv=None):
    cfg = ctx["cfg"]
    if kv is None:
        q, k, v = attn_qkv(params, h, cfg, ctx, kv_source=ctx["cross_states"])
    else:
        q = _split_heads(
            mp_dot(h, params["wq"], policy=ctx["policy"]), cfg.n_heads, cfg.head_dim)
        k, v = kv
    o = attn.attention_core(q, k.astype(q.dtype), v.astype(q.dtype),
                            causal=False, backend=ctx.get("backend"))
    return mp_dot(_merge_heads(o), params["wo"], policy=ctx["policy"]), (k, v)


def cross_fwd(params, x, ctx):
    cfg = ctx["cfg"]
    o, kv = _cross_attention(params["xattn"], norm(params["ln1"], x, cfg), ctx)
    x = x + jnp.tanh(params["gate_attn"]).astype(o.dtype) * o
    m = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"])
    x = x + jnp.tanh(params["gate_mlp"]).astype(m.dtype) * m
    cache = None
    if ctx.get("collect_cache"):
        dt = ctx.get("cache_dtype", jnp.bfloat16)
        cache = {"k": kv[0].astype(dt), "v": kv[1].astype(dt)}
    return x, ZERO, cache


def cross_decode(params, x, cache, ctx):
    cfg = ctx["cfg"]
    o, _ = _cross_attention(params["xattn"], norm(params["ln1"], x, cfg), ctx,
                            kv=(cache["k"], cache["v"]))
    x = x + jnp.tanh(params["gate_attn"]).astype(o.dtype) * o
    m = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"])
    x = x + jnp.tanh(params["gate_mlp"]).astype(m.dtype) * m
    return x, cache


def cross_init_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    shape = (batch, cfg.n_kv_heads, cfg.n_image_tokens, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# =============================== MoE ===========================================

def _expert_dot(ebuf, w, policy, **fusion):
    """(e, n, d) x (e, d, f) -> (e, n, f) through the grouped MPGEMM op.

    One kernel launch for all E experts (group = leading grid axis), under
    the layer policy with f32 outputs (accumulator precision is kept
    between the expert GEMMs and the combine).  The op's custom VJP runs
    the backward contractions as fused-transpose grouped GEMMs with bf16
    partial sums on the XLA backend, so the dbuf/dW EP/TP all-reduces move
    bf16 on the wire (which einsum-based dispatch could not express).

    ``fusion`` forwards registry-epilogue operands (``activation=``,
    ``gate=`` — core/gemm_spec.py), which is how the MoE SwiGLU gating
    rides the gate GEMM's store below.

    ``w`` may be a grouped :class:`repro.packing.PackedOperand` — expert
    weights packed once at load time (``pack_params``): mp_dot_grouped
    then reads the pre-tiled per-expert payload with identity index maps
    instead of re-laying the experts out on every launch.  It may also be
    a grouped :class:`repro.sparse.TileSparseOperand` (``sparsify_params``)
    — the launch then walks only the union of every expert's stored tiles,
    so tile-pruned experts shrink the grid itself."""
    return mp_dot_grouped(ebuf, w, policy=policy, out_dtype=jnp.float32,
                          **fusion)


# The expert weights of a MoE block, (E, ., .) each.
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def init_moe(key, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale = (2.0 / (d + f)) ** 0.5
    return {
        "ln1": init_norm(cfg), "attn": init_attn(k1, cfg),
        "ln2": init_norm(cfg),
        "router": dense_init(k2, d, e),
        "w_gate": (jax.random.normal(k3, (e, d, f)) * scale).astype(jnp.float32),
        "w_up": (jax.random.normal(k4, (e, d, f)) * scale).astype(jnp.float32),
        "w_down": (jax.random.normal(k5, (e, f, d)) * scale).astype(jnp.float32),
    }


def moe_mlp(params, x, cfg, policy, capacity_factor: float = 1.25):
    """Top-k MoE with GROUP-LOCAL sort-based dispatch.

    Groups = sequences (the batch dim), which is the data-sharded axis, so
    the argsort/scatter dispatch never crosses shards — no global sort
    collectives.  The expert GEMMs run as grouped MPGEMM launches
    (mp_dot_grouped: group = expert, K-innermost accumulator, fused-
    transpose backward) contracting (e, b*C, d) x (e, d, f); with
    experts sharded over 'model' (EP) GSPMD inserts the all-to-all style
    resharding between the data-sharded buffer and model-sharded experts,
    exactly the EP communication pattern.  Gathers/scatters carry no fake
    FLOPs into the roofline (vs. one-hot dispatch einsums).
    Returns (out, aux_scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = mp_dot(x, params["router"], policy="fp32").astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                 # (b,s,e)
    topw, topi = jax.lax.top_k(gates, k)                    # (b,s,k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(round(capacity_factor * k * s / e)))

    def route(tokens, ti, tw):
        """Per-sequence dispatch: tokens (s,d), ti/tw (s,k).

        GATHER-based: the only scatters are tiny int32 index maps; the
        (e*C, d) payload moves via gathers (scatter lowering on big payload
        buffers costs full-buffer sort passes + index companions).

        Capacity slots are assigned NEWEST-token-first, so under overflow
        the most recent positions (the ones decode consistency depends on)
        keep their experts."""
        rev = jnp.arange(s - 1, -1, -1)
        slot_e = ti[rev].reshape(-1)                        # (s*k,)
        slot_t = jnp.repeat(rev, k)
        order = jnp.argsort(slot_e)
        se, st = slot_e[order], slot_t[order]
        counts = jnp.bincount(slot_e, length=e)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(s * k) - starts[se]
        keep = rank < cap
        dest = jnp.where(keep, se * cap + rank, e * cap)    # overflow slot
        # slot -> source token (int32 scatter, payload-free)
        src = jnp.full((e * cap + 1,), s, jnp.int32).at[dest].set(
            st.astype(jnp.int32))[:-1]
        tok_pad = jnp.concatenate([tokens, jnp.zeros((1, d), tokens.dtype)])
        buf = tok_pad[src]                                  # payload gather
        # (token,choice) -> slot, back in original token order
        dest_tok = jnp.zeros((s * k,), jnp.int32).at[order].set(
            dest.astype(jnp.int32)).reshape(s, k)[rev].reshape(-1)
        return buf.reshape(e, cap, d), dest_tok

    buf, dest_tok = jax.vmap(route)(x, topi, topw)          # (b,e,C,d)
    buf = act.constrain(buf, "batch", None, None, None)

    # Fold b into the capacity dim: ONE grouped GEMM (e, b*C, d) x (e, d, f)
    # per projection — group = expert — through mp_dot_grouped, which owns
    # the policy cast, static-int8 dequant, and the shard-local down-cast
    # barrier (inside its custom VJP, where no differentiation rule for the
    # barrier is ever needed).
    ebuf = buf.transpose(1, 0, 2, 3).reshape(e, b * cap, d)
    h_up = _expert_dot(ebuf, params["w_up"], policy)
    if gemm_cfg.fused_epilogues():
        # Gated epilogue: silu(gate GEMM) · up rides the gate GEMM's
        # accumulator store — one grouped launch, no elementwise pass.
        h = _expert_dot(ebuf, params["w_gate"], policy,
                        activation="silu", gate=h_up)
    else:
        h_gate = _expert_dot(ebuf, params["w_gate"], policy)
        h = jax.nn.silu(h_gate) * h_up                      # f32 activations
    y = _expert_dot(h, params["w_down"], policy)  # (e,n,f) x (e,f,d) -> (e,n,d)
    y = y.reshape(e, b, cap, d).transpose(1, 0, 2, 3)       # (b,e,C,d)

    def combine(y_g, dest_tok_g, tw_g):
        """Pure-gather combine: out[t] = sum_j w_j * y[slot(t, j)]."""
        flat = y_g.reshape(e * cap, d)
        y_pad = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)])
        contrib = y_pad[dest_tok_g].reshape(s, k, d)        # payload gather
        kept = (dest_tok_g < e * cap).reshape(s, k).astype(jnp.float32)
        w = tw_g.astype(jnp.float32) * kept
        return jnp.einsum("skd,sk->sd", contrib.astype(jnp.float32), w)

    out = jax.vmap(combine)(y, dest_tok, topw)              # (b,s,d)

    me = gates.mean((0, 1))
    ce = jnp.bincount(topi.reshape(-1), length=e).astype(jnp.float32) / (b * s * k)
    aux = e * jnp.sum(me * ce)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out.astype(x.dtype), 0.01 * aux + 0.001 * zloss


def moe_mlp_ragged(params, x, live, cfg, policy):
    """Dropless top-k MoE over the live tokens of a served step.

    With ``params["layer"]`` (an int32 scalar) the expert weights are the
    (L, E, ., .) stacks of a layer scan and layer ``layer`` of them is
    read in place (``LM.paged_step`` hoists them out of its scan).

    ``x`` (B, C, d), ``live`` (B, C) bool.  Every token is routed (the
    router GEMM and top-k as in :func:`moe_mlp`), but only the live
    tokens' (token, expert) pairs take rows: a counting sort by expert
    (dead pairs keyed past the last expert) places them in ONE row buffer
    whose expert segments each start on a row tile of ``bm`` rows
    (``kernels/mpgemm.py::ragged_block_rows``), with the tile map of the
    buffer's live tiles.  The three expert GEMMs (:func:`mp_dot_ragged`)
    compute only those tiles, reading each routed expert's weights once;
    the combine gathers each live pair's output row and weights it by its
    top-k weight.  No capacity exists, so nothing is dropped for any
    ``top_k / n_experts``; dead tokens get zeros.  Returns (B, C, d)."""
    b, c, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * c
    xf = x.reshape(t, d)
    logits = mp_dot(xf, params["router"], policy="fp32").astype(jnp.float32)
    topw, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)   # (t,k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    live = live.reshape(t)
    # Counting sort by expert: a token picks each expert at most once, so
    # a live pair's rank in its expert is how many earlier live tokens
    # picked that expert.
    picked = (jnp.any(topi[:, :, None] == jnp.arange(e), axis=1)
              & live[:, None]).astype(jnp.int32)                      # (t,e)
    rank = jnp.cumsum(picked, axis=0) - picked
    bm = ragged_block_rows(t * k, e)
    r = ragged_rows(t * k, e)
    start, tiles = ragged_layout(picked.sum(0), bm, r // bm)
    row = jnp.where(live[:, None],
                    start[topi] + jnp.take_along_axis(rank, topi, axis=1),
                    r).reshape(-1)                                    # (t*k,)
    # row -> source token (int32 scatter); padding rows read zeros.
    src = jnp.full((r,), t, jnp.int32).at[row].set(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
    buf = jnp.take(xf, src, axis=0, mode="fill", fill_value=0)        # (r,d)

    def expert_dot(a, w, **fusion):
        return mp_dot_ragged(a, w, tiles, policy=policy,
                             layer=params.get("layer"),
                             out_dtype=jnp.float32, **fusion)

    h_up = expert_dot(buf, params["w_up"])
    if gemm_cfg.fused_epilogues():
        h = expert_dot(buf, params["w_gate"], activation="silu", gate=h_up)
    else:
        h = jax.nn.silu(expert_dot(buf, params["w_gate"])) * h_up
    y = expert_dot(h, params["w_down"])                               # (r,d)
    contrib = jnp.take(y, row, axis=0, mode="fill",
                       fill_value=0).reshape(t, k, d)
    w = jnp.where(live[:, None], topw, 0.0)
    out = jnp.einsum("tkd,tk->td", contrib, w)
    return out.reshape(b, c, d).astype(x.dtype)


def moe_fwd(params, x, ctx, *, window=None):
    cfg = ctx["cfg"]
    o, kv = _self_attention(params["attn"], norm(params["ln1"], x, cfg), ctx, window)
    x = x + o
    y, aux = moe_mlp(params, norm(params["ln2"], x, cfg), cfg, ctx["policy"],
                     capacity_factor=ctx.get("moe_capacity", 1.25))
    x = x + y
    cache = None
    if kv is not None:
        cache = _kv_to_ring_cache(kv, ctx["cache_len"] if window is None
                                  else min(window, ctx["cache_len"]),
                                  ctx.get("cache_dtype", jnp.bfloat16))
    return x, aux, cache


def moe_decode(params, x, cache, ctx):
    cfg = ctx["cfg"]
    o, cache = attn_decode(params["attn"], norm(params["ln1"], x, cfg), cache, ctx)
    x = x + o
    y, _ = moe_mlp(params, norm(params["ln2"], x, cfg), cfg, ctx["policy"],
                   capacity_factor=ctx.get("moe_capacity", 1.25))
    return x + y, cache


def moe_paged_step(params, x, cache, ctx, *, window=None):
    cfg = ctx["cfg"]
    o, cache = attn_paged_step(params["attn"], norm(params["ln1"], x, cfg),
                               cache, ctx, window=window)
    x = x + o
    pg = ctx["paged"]
    live = jnp.arange(x.shape[1])[None, :] < pg["n_valid"][:, None]
    y = moe_mlp_ragged(params, norm(params["ln2"], x, cfg), live, cfg,
                       ctx["policy"])
    return x + y, cache


def moe_init_cache(cfg, batch, max_len, dtype=jnp.bfloat16, window=None):
    return attn_init_cache(cfg, batch, max_len, dtype, window=window)


# =============================== enc-dec (whisper) =============================

def init_encdec(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_norm(cfg), "attn": init_attn(k1, cfg),
        "lnx": init_norm(cfg), "xattn": init_attn(k2, cfg),
        "ln2": init_norm(cfg), "mlp": _init_mlp(k3, cfg),
    }


def encdec_fwd(params, x, ctx):
    """Decoder block: causal self-attn + cross-attn to encoder states."""
    cfg = ctx["cfg"]
    o, kv = _self_attention(params["attn"], norm(params["ln1"], x, cfg), ctx, None)
    x = x + o
    o, xkv = _cross_attention(params["xattn"], norm(params["lnx"], x, cfg), ctx)
    x = x + o
    x = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"],
             residual=x)
    cache = None
    if kv is not None:
        dt = ctx.get("cache_dtype", jnp.bfloat16)
        cache = {"self": _kv_to_ring_cache(kv, ctx["cache_len"], dt),
                 "cross": {"k": xkv[0].astype(dt), "v": xkv[1].astype(dt)}}
    return x, ZERO, cache


def encdec_decode(params, x, cache, ctx):
    cfg = ctx["cfg"]
    o, self_cache = attn_decode(params["attn"], norm(params["ln1"], x, cfg),
                                cache["self"], ctx)
    x = x + o
    o, _ = _cross_attention(params["xattn"], norm(params["lnx"], x, cfg), ctx,
                            kv=(cache["cross"]["k"], cache["cross"]["v"]))
    x = x + o
    x = _mlp(params["mlp"], norm(params["ln2"], x, cfg), cfg, ctx["policy"],
             residual=x)
    return x, {"self": self_cache, "cross": cache["cross"]}


def encdec_init_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    return {
        "self": attn_init_cache(cfg, batch, max_len, dtype),
        "cross": {"k": jnp.zeros((batch, cfg.n_kv_heads, cfg.encoder_seq,
                                  cfg.head_dim), dtype),
                  "v": jnp.zeros((batch, cfg.n_kv_heads, cfg.encoder_seq,
                                  cfg.head_dim), dtype)},
    }
