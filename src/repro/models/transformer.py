"""Model assembly: pattern-segmented layer stacks (scan over repeating units)
and the LM class exposing init / loss_fn / prefill / decode_step.

Heterogeneous architectures (vision cross-attn every 5th layer,
recurrentgemma's rglru/rglru/attn pattern) are handled by finding the
smallest repeating *unit* of the block pattern and scanning over units, with
any remainder layers applied unscanned — HLO stays compact (one unit body)
regardless of depth, which keeps 56-layer × 512-device AOT compiles cheap.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed import act
from repro.models import blocks as B
from repro.models import recurrent as R
from repro.models.layers import (
    embed_init, logits_from_hidden, rope_frequencies,
)
from repro.models.losses import chunked_softmax_xent

INIT = {
    "dense": B.init_dense,
    "attn_local": B.init_dense,
    "moe": B.init_moe,
    "cross": B.init_cross,
    "encdec": B.init_encdec,
    "rwkv": R.init_rwkv,
    "rglru": R.init_rglru,
}


def _kind_window(cfg, kind):
    if kind == "attn_local":
        return cfg.local_attn_window
    return cfg.window


def block_fwd(kind, params, x, ctx):
    cfg = ctx["cfg"]
    if kind in ("dense", "attn_local"):
        return B.dense_fwd(params, x, ctx, window=_kind_window(cfg, kind))
    if kind == "moe":
        return B.moe_fwd(params, x, ctx, window=cfg.window)
    if kind == "cross":
        return B.cross_fwd(params, x, ctx)
    if kind == "encdec":
        return B.encdec_fwd(params, x, ctx)
    if kind == "rwkv":
        return R.rwkv_fwd(params, x, ctx)
    if kind == "rglru":
        return R.rglru_fwd(params, x, ctx)
    raise ValueError(kind)


def block_decode(kind, params, x, cache, ctx):
    if kind in ("dense", "attn_local"):
        return B.dense_decode(params, x, cache, ctx)
    if kind == "moe":
        return B.moe_decode(params, x, cache, ctx)
    if kind == "cross":
        return B.cross_decode(params, x, cache, ctx)
    if kind == "encdec":
        return B.encdec_decode(params, x, cache, ctx)
    if kind == "rwkv":
        return R.rwkv_decode(params, x, cache, ctx)
    if kind == "rglru":
        return R.rglru_decode(params, x, cache, ctx)
    raise ValueError(kind)


def block_init_cache(kind, cfg, batch, max_len, dtype=jnp.bfloat16):
    if kind in ("dense", "attn_local"):
        return B.dense_init_cache(cfg, batch, max_len, dtype,
                                  window=_kind_window(cfg, kind))
    if kind == "moe":
        return B.moe_init_cache(cfg, batch, max_len, dtype, window=cfg.window)
    if kind == "cross":
        return B.cross_init_cache(cfg, batch, max_len, dtype)
    if kind == "encdec":
        return B.encdec_init_cache(cfg, batch, max_len, dtype)
    if kind == "rwkv":
        return R.rwkv_init_cache(cfg, batch, max_len, dtype)
    if kind == "rglru":
        return R.rglru_init_cache(cfg, batch, max_len, dtype)
    raise ValueError(kind)


# Block kinds the paged-KV serving path supports (recurrent state and
# cross-attention caches are not paged — those archs serve via the wave
# engine; see docs/serving.md).
PAGED_KINDS = ("dense", "attn_local", "moe")


def block_paged_step(kind, params, x, cache, ctx):
    cfg = ctx["cfg"]
    if kind in ("dense", "attn_local"):
        return B.dense_paged_step(params, x, cache, ctx,
                                  window=_kind_window(cfg, kind))
    if kind == "moe":
        return B.moe_paged_step(params, x, cache, ctx, window=cfg.window)
    raise ValueError(
        f"block kind {kind!r} has no paged-KV step (supported: "
        f"{PAGED_KINDS}); serve this arch with the wave engine")


def segment_pattern(pattern: Tuple[str, ...]):
    """-> (unit, n_units, remainder): smallest unit P<=8 such that the
    pattern is unit-periodic with a unit-prefix remainder."""
    L = len(pattern)
    for p in range(1, min(8, L) + 1):
        n_units = L // p
        if n_units == 0:
            continue
        if all(pattern[i] == pattern[i % p] for i in range(n_units * p)):
            rem = pattern[n_units * p:]
            if all(rem[i] == pattern[i] for i in range(len(rem))):
                return pattern[:p], n_units, rem
    return pattern, 1, ()


@dataclasses.dataclass
class LM:
    """Decoder LM (optionally with encoder / cross-attention inputs)."""

    cfg: ArchConfig
    policy: str = "bf16"
    remat: bool = True
    act_dtype: Any = None

    def __post_init__(self):
        self.unit, self.n_units, self.rem = segment_pattern(self.cfg.pattern)
        if self.act_dtype is None:
            from repro.core.policy import get_policy
            self.act_dtype = jnp.dtype(get_policy(self.policy).out_dtype)

    # ------------------------------ init ------------------------------------

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to 128 so embed/head shard cleanly over the
        'model' axis (granite's 49155, whisper's 51865...).  The loss and
        serve logits mask the padding."""
        return ((self.cfg.vocab + 127) // 128) * 128

    def init(self, key) -> Dict[str, Any]:
        """Parameters in the policy's storage dtype (``param_dtype``).

        f32 storage (fp32, bf16 with f32 masters) builds the tree as is.
        A narrower storage dtype casts every float leaf inside ONE jitted
        program, so each leaf leaves the device already narrowed and no
        f32 copy of the stack ever exists (phi3-mini is 15 GB in f32).
        """
        from repro.core.policy import get_policy
        dtype = jnp.dtype(get_policy(self.policy).param_dtype)
        if dtype == jnp.float32:
            return self._init_f32(key)

        def narrowed(k):
            return jax.tree_util.tree_map(
                lambda a: a.astype(dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                self._init_f32(k))

        return jax.jit(narrowed)(key)

    def _init_f32(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        vp = self.vocab_padded
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": embed_init(keys[0], vp, cfg.d_model),
            "final_norm": B.init_norm(cfg),
        }
        if not cfg.tie_embeddings:
            params["head"] = (
                jax.random.normal(keys[1], (cfg.d_model, vp)) * 0.02
            ).astype(jnp.float32)
        if cfg.pos_embed == "learned":
            params["pos_embed"] = embed_init(keys[2], 32768 + 8, cfg.d_model)
        # main stack
        stack = []
        for p, kind in enumerate(self.unit):
            ks = jax.random.split(jax.random.fold_in(keys[3], p), self.n_units)
            stack.append(jax.vmap(lambda k: INIT[kind](k, cfg))(ks))
        params["stack"] = stack
        params["tail"] = [
            INIT[kind](jax.random.fold_in(keys[4], i), cfg)
            for i, kind in enumerate(self.rem)
        ]
        if cfg.encoder_layers:
            ks = jax.random.split(keys[5], cfg.encoder_layers)
            params["encoder"] = jax.vmap(lambda k: B.init_dense(k, cfg))(ks)
            params["enc_norm"] = B.init_norm(cfg)
            params["enc_pos"] = embed_init(keys[6], cfg.encoder_seq, cfg.d_model)
        return params

    # ------------------------------ helpers ---------------------------------

    def _ctx(self, seq_len, *, collect_cache=False, cache_len=0, pos=None,
             cross_states=None, rope_rows=None):
        cfg = self.cfg
        rope = None
        if cfg.pos_embed == "rope":
            if rope_rows is not None:
                rope = rope_rows          # precomputed rows (decode)
            else:
                rope = rope_frequencies(cfg.head_dim, seq_len, cfg.rope_theta)
        return {
            "cfg": cfg, "policy": self.policy, "backend": None,
            "rope": rope, "positions": None, "causal": cfg.causal,
            "collect_cache": collect_cache, "cache_len": cache_len,
            "cache_dtype": self.act_dtype, "pos": pos,
            "cross_states": cross_states,
        }

    def _decode_rope(self, pos):
        cfg = self.cfg
        hd = cfg.head_dim
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos.astype(jnp.float32) * inv          # (hd/2,)
        return jnp.cos(ang)[None], jnp.sin(ang)[None]  # single-row tables

    def _embed(self, params, tokens):
        x = params["embed"][tokens].astype(self.act_dtype)
        if self.cfg.pos_embed == "learned":
            t = tokens.shape[1]
            x = x + params["pos_embed"][:t][None].astype(self.act_dtype)
        return act.constrain(x, "batch", None, None)

    def _encode(self, params, audio_embeds):
        """Whisper encoder: non-causal dense stack over stubbed frame embeds."""
        cfg = self.cfg
        x = audio_embeds.astype(self.act_dtype)
        x = x + params["enc_pos"][: x.shape[1]][None].astype(self.act_dtype)
        ctx = self._ctx(x.shape[1])
        ctx["causal"] = False
        ctx["rope"] = None

        def body(x, layer_params):
            y, _, _ = B.dense_fwd(layer_params, x, ctx, window=None)
            return y, None

        body = jax.checkpoint(body) if self.remat else body
        x, _ = jax.lax.scan(body, x, params["encoder"])
        return B.norm(params["enc_norm"], x, cfg)

    def _run_stack(self, params, x, ctx):
        """-> (x, aux, caches|None)"""
        unit, rem = self.unit, self.rem
        collect = ctx["collect_cache"]

        def body(carry, unit_params):
            x, aux = carry
            caches = []
            for p, kind in enumerate(unit):
                x, a, c = block_fwd(kind, unit_params[p], x, ctx)
                # Constraining x to ('batch','model',None) here (a
                # sequence-parallel residual) makes GSPMD reshard around
                # every block-internal op instead of forming
                # reduce-scatter/all-gather pairs.
                x = act.constrain(x, "batch", None, None)
                aux = aux + a
                caches.append(c)
            return (x, aux), (caches if collect else 0)

        scan_body = jax.checkpoint(body) if self.remat else body
        (x, aux), stack_caches = jax.lax.scan(
            scan_body, (x, jnp.float32(0.0)), params["stack"])
        tail_caches = []
        for i, kind in enumerate(rem):
            x, a, c = block_fwd(kind, params["tail"][i], x, ctx)
            aux = aux + a
            tail_caches.append(c)
        caches = None
        if collect:
            caches = {"stack": stack_caches, "tail": tail_caches}
        return x, aux, caches

    def _final_hidden(self, params, x):
        return B.norm(params["final_norm"], x, self.cfg)

    def _head(self, params):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return params["embed"], True
        return params["head"], False

    def _cross_states(self, params, batch):
        if self.cfg.family == "vlm":
            return batch["image_embeds"].astype(self.act_dtype)
        if self.cfg.family == "audio":
            return self._encode(params, batch["audio_embeds"])
        return None

    # ------------------------------ training --------------------------------

    def loss_fn(self, params, batch):
        """batch: tokens (B, S+1) [+ image_embeds / audio_embeds]."""
        tokens = batch["tokens"]
        x = self._embed(params, tokens[:, :-1])
        labels = tokens[:, 1:]
        ctx = self._ctx(x.shape[1])
        ctx["cross_states"] = self._cross_states(params, batch)
        x, aux, _ = self._run_stack(params, x, ctx)
        x = self._final_hidden(params, x)
        head, tied = self._head(params)
        loss = chunked_softmax_xent(x, head, labels, tied=tied,
                                    policy=self.policy,
                                    valid_vocab=self.cfg.vocab)
        return loss + aux

    # ------------------------------ serving ---------------------------------

    def init_caches(self, batch_size: int, max_len: int):
        caches_stack = []
        for p, kind in enumerate(self.unit):
            one = block_init_cache(kind, self.cfg, batch_size, max_len,
                                   self.act_dtype)
            stacked = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.n_units,) + a.shape), one)
            caches_stack.append(stacked)
        tail = [block_init_cache(kind, self.cfg, batch_size, max_len,
                                 self.act_dtype) for kind in self.rem]
        return {"stack": caches_stack, "tail": tail}

    def prefill(self, params, batch, max_len: Optional[int] = None):
        """-> (last-token logits (B, V), caches)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        max_len = max_len or s
        x = self._embed(params, tokens)
        ctx = self._ctx(s, collect_cache=True, cache_len=max_len)
        ctx["moe_capacity"] = 4.0   # serve-time: effectively dropless
        ctx["cross_states"] = self._cross_states(params, batch)
        x, _, caches = self._run_stack(params, x, ctx)
        x = self._final_hidden(params, x[:, -1:])
        head, tied = self._head(params)
        logits = self._mask_logits(
            logits_from_hidden(x, head, tied=tied, policy=self.policy))
        return logits[:, 0], caches

    def _mask_logits(self, logits):
        vp = self.vocab_padded
        if vp == self.cfg.vocab:
            return logits
        valid = jnp.arange(vp) < self.cfg.vocab
        return jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))

    def decode_step(self, params, token, caches, pos, batch=None):
        """token: (B, 1) int32; pos: scalar int32 count of tokens seen.
        -> (logits (B, V), new caches)."""
        cfg = self.cfg
        x = params["embed"][token].astype(self.act_dtype)
        if cfg.pos_embed == "learned":
            pe = params["pos_embed"][
                jnp.minimum(pos, params["pos_embed"].shape[0] - 1)]
            x = x + pe[None, None].astype(self.act_dtype)
        rope_rows = self._decode_rope(pos) if cfg.pos_embed == "rope" else None
        ctx = self._ctx(1, pos=pos, rope_rows=rope_rows)
        ctx["rope_single_row"] = True
        ctx["moe_capacity"] = 4.0   # serve-time: effectively dropless

        def body(x, xs):
            unit_params, unit_caches = xs
            new = []
            for p, kind in enumerate(self.unit):
                x, c = block_decode(kind, unit_params[p], x, unit_caches[p], ctx)
                new.append(c)
            return x, new

        x, new_stack = jax.lax.scan(
            body, x, (params["stack"], caches["stack"]))
        new_tail = []
        for i, kind in enumerate(self.rem):
            x, c = block_decode(kind, params["tail"][i], x,
                                caches["tail"][i], ctx)
            new_tail.append(c)
        x = self._final_hidden(params, x)
        head, tied = self._head(params)
        logits = self._mask_logits(
            logits_from_hidden(x, head, tied=tied, policy=self.policy))
        return logits[:, 0], {"stack": new_stack, "tail": new_tail}

    # ------------------------- paged serving (continuous batching) ----------

    def paged_unsupported_reason(self) -> Optional[str]:
        """None if every block kind has a paged-KV step, else why not."""
        bad = sorted({k for k in (*self.unit, *self.rem)
                      if k not in PAGED_KINDS})
        if bad:
            return (f"block kinds {bad} have no paged-KV step (supported: "
                    f"{PAGED_KINDS}); serve this arch with the wave engine")
        return None

    def init_paged_caches(self, num_pages: int, page_size: int):
        """Pooled KV pages, one ``kv_page_shape(num_pages, Hkv, page_size,
        hd)`` pair per layer; block tables are shared across layers so the
        layer axis lives here, exactly like init_caches stacks ring
        caches."""
        reason = self.paged_unsupported_reason()
        if reason:
            raise ValueError(reason)
        caches_stack = []
        for _kind in self.unit:
            one = B.attn_paged_init_cache(self.cfg, num_pages, page_size,
                                          self.act_dtype)
            caches_stack.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.n_units,) + a.shape),
                one))
        tail = [B.attn_paged_init_cache(self.cfg, num_pages, page_size,
                                        self.act_dtype) for _ in self.rem]
        return {"stack": caches_stack, "tail": tail}

    def paged_step(self, params, tokens, caches, block_tables, q_start,
                   n_valid):
        """One continuous-batching step over paged KV.

        tokens: (B, C) int32 — C == 1 is a pure decode step, C > 1 a
        chunked-prefill step (decode rows just use one valid column).
        block_tables: (B, W) int32 physical page ids (0 = scratch pad);
        q_start: (B,) absolute position of each row's first token;
        n_valid: (B,) valid tokens per row (0 = idle slot).
        -> (logits (B, V) at each row's LAST valid token, new caches).
        """
        cfg = self.cfg
        b, c = tokens.shape
        ps = caches["stack"][0]["k_pages"].shape[-2] if caches["stack"] \
            else caches["tail"][0]["k_pages"].shape[-2]
        w = block_tables.shape[1]
        max_pos = w * ps
        positions = jnp.clip(
            q_start[:, None] + jnp.arange(c)[None, :], 0, max_pos - 1)
        x = params["embed"][tokens].astype(self.act_dtype)
        if cfg.pos_embed == "learned":
            pe = params["pos_embed"]
            x = x + pe[jnp.minimum(positions, pe.shape[0] - 1)].astype(
                self.act_dtype)
        rope_rows = None
        if cfg.pos_embed == "rope":
            rope_rows = rope_frequencies(cfg.head_dim, max_pos,
                                         cfg.rope_theta)
        ctx = self._ctx(c, rope_rows=rope_rows)
        ctx["positions"] = positions
        ctx["paged"] = {
            "block_tables": block_tables.astype(jnp.int32),
            "q_start": q_start.astype(jnp.int32),
            "n_valid": n_valid.astype(jnp.int32),
            "lengths": (q_start + n_valid).astype(jnp.int32),
        }

        # The MoE expert weight stacks stay out of the scan's slices: the
        # ragged expert launch reads only the routed experts of layer i
        # in place, where a per-layer slice would copy all E experts.
        stacks = [{k: unit[k] for k in B.EXPERT_WEIGHTS}
                  if kind == "moe" and all(isinstance(unit[k], jax.Array)
                                           for k in B.EXPERT_WEIGHTS)
                  else {}
                  for kind, unit in zip(self.unit, params["stack"])]
        sliced = [{k: v for k, v in unit.items() if k not in hoisted}
                  for unit, hoisted in zip(params["stack"], stacks)]

        def body(x, xs):
            unit_params, unit_caches, i = xs
            new = []
            for p, kind in enumerate(self.unit):
                bp = unit_params[p]
                if stacks[p]:
                    bp = {**bp, **stacks[p], "layer": i}
                x, cc = block_paged_step(kind, bp, x, unit_caches[p], ctx)
                new.append(cc)
            return x, new

        x, new_stack = jax.lax.scan(
            body, x, (sliced, caches["stack"],
                      jnp.arange(self.n_units, dtype=jnp.int32)))
        new_tail = []
        for i, kind in enumerate(self.rem):
            x, cc = block_paged_step(kind, params["tail"][i], x,
                                     caches["tail"][i], ctx)
            new_tail.append(cc)
        # Each row's next-token logits live at its LAST valid position
        # (idle rows clamp to column 0 — the engine ignores them).
        idx = jnp.clip(n_valid - 1, 0, c - 1)
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)   # (B, 1, d)
        x = self._final_hidden(params, x)
        head, tied = self._head(params)
        logits = self._mask_logits(
            logits_from_hidden(x, head, tied=tied, policy=self.policy))
        return logits[:, 0], {"stack": new_stack, "tail": new_tail}


def build_model(cfg: ArchConfig, policy: str = "bf16", remat: bool = True) -> LM:
    # audio (enc-dec) archs use the 'encdec' block kind for decoder layers.
    if cfg.family == "audio" and not cfg.block_pattern:
        cfg = dataclasses.replace(cfg, block_pattern=("encdec",) * cfg.n_layers)
    return LM(cfg, policy=policy, remat=remat)
