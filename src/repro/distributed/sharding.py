"""Logical sharding rules -> NamedSharding for every param / input / cache.

Scheme:
  * 'model' axis: tensor parallelism — d_ff, attention-head projections,
    vocab dim of embed/head, expert dim (EP) when divisible, KV-cache
    sequence dim (sequence-parallel decode).
  * 'data' axis: data parallelism for activations AND FSDP for weights —
    every weight matrix also shards its non-TP dim over 'data', so optimizer
    state is fully sharded (ZeRO-3 flavored; XLA inserts the per-layer
    weight all-gathers).
  * 'pod' axis (multi-pod mesh): pure DP — batch sharded, weights replicated
    across pods, gradients all-reduced hierarchically.

Every rule is divisibility-guarded: a dim that does not divide by its mesh
axis falls back to replication on that axis (e.g. granite's vocab 49155,
whisper's encoder_seq 1500).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _guard(mesh: Mesh, spec: Tuple, shape: Tuple[int, ...]) -> P:
    """Drop axes whose size does not divide the corresponding dim."""
    fixed = []
    for dim, axis in zip(shape, spec):
        if axis is not None and dim % _axis_size(mesh, axis) == 0:
            fixed.append(axis)
        else:
            fixed.append(None)
    return P(*fixed)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def batch_axes(mesh: Mesh):
    """The composite data-parallel axis: ('pod','data') on multi-pod."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def param_pspec(path: str, shape: Tuple[int, ...], cfg, mesh: Mesh) -> P:
    """Sharding rule for one parameter leaf, identified by its tree path."""
    d = cfg.d_model
    parts = path.split("/")
    name = parts[-1]
    if name == "q" and len(parts) >= 2:   # static-int8 weight payload
        name = parts[-2]
    elif name == "scale" and len(parts) >= 2 and parts[-2] in (
            "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ck", "cv",
            "cr", "wr", "wg", "w_x", "w_y", "w_out", "w_gate_r", "w_gate_i",
            "head"):
        return P()                         # scalar scale: replicated
    # Leading stacked-layer dim (scan units) is never sharded.
    stacked = path.split("/")[0] in ("stack", "encoder") or "stack/" in path
    lead = (None,) if (stacked and len(shape) >= 1) else ()
    core_shape = shape[len(lead):]

    def spec(*axes):
        return _guard(mesh, lead + axes, shape)

    if name in ("embed",):
        return spec("model", "data")
    if name == "head":
        return spec("data", "model")
    if name in ("pos_embed", "enc_pos"):
        return spec(None, "data")
    if name in ("scale", "bias", "w_base", "lambda_p", "conv_b", "b_down",
                "gate_attn", "gate_mlp", "mu", "mu_c", "u", "gn_scale"):
        return spec(*([None] * len(core_shape)))
    if name == "b_up":
        return spec("model")
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "ck", "cr", "wr",
                "w_x", "w_y", "w_gate_r", "w_gate_i", "lora_a", "w_lora_a"):
        if len(core_shape) == 3:  # MoE experts (E, d, f)
            if core_shape[0] % _axis_size(mesh, "model") == 0:
                return spec("model", "data", None)      # expert parallelism
            return spec(None, "data", "model")          # TP within experts
        return spec("data", "model")
    if name in ("wo", "w_down", "cv", "w_out", "w_lora_b"):
        if len(core_shape) == 3:  # (E, f, d)
            if core_shape[0] % _axis_size(mesh, "model") == 0:
                return spec("model", None, "data")
            return spec(None, "model", "data")
        return spec("model", "data")
    if name in ("router",):
        return spec("data", None)
    if name in ("conv_w",):
        return spec(None, "model")
    if name in ("lora_b",):
        return spec(None, None, "data")
    if name in ("wg", "wk2",):
        return spec("data", "model")
    # Default: replicate.
    return P(*([None] * len(shape)))


def params_shardings(params_shape: Any, cfg, mesh: Mesh):
    """Pytree of NamedShardings matching a params (shape-)pytree."""

    def one(path, leaf):
        return NamedSharding(
            mesh, param_pspec(_path_str(path), leaf.shape, cfg, mesh))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_pspec(path: str, shape: Tuple[int, ...], cfg, mesh: Mesh) -> P:
    """KV caches: batch over data axes, sequence dim over 'model'
    (sequence-parallel decode; softmax reductions over the sharded KV axis
    become all-reduces).  Recurrent states: width/head dims over 'model'."""
    da = batch_axes(mesh)
    da = da if len(da) > 1 else da[0]
    name = path.split("/")[-1]
    stacked = path.startswith("stack")
    lead = (None,) if stacked else ()

    def spec(*axes):
        return _guard(mesh, lead + axes, shape)

    core = shape[len(lead):]
    if name in ("k", "v") and len(core) == 4:      # (B, Hkv, S, hd)
        return spec(da, None, "model", None)
    if name == "state" and len(core) == 4:          # rwkv (B, H, dk, dv)
        return spec(da, "model", None, None)
    if name in ("shift_t", "shift_c", "h"):         # (B, d|w)
        return spec(da, "model")
    if name == "conv":                               # (B, K-1, w)
        return spec(da, None, "model")
    return spec(da, *([None] * (len(core) - 1)))


def caches_shardings(caches_shape: Any, cfg, mesh: Mesh):
    def one(path, leaf):
        return NamedSharding(
            mesh, cache_pspec(_path_str(path), leaf.shape, cfg, mesh))

    return jax.tree_util.tree_map_with_path(one, caches_shape)


def batch_shardings(batch_shape: Any, mesh: Mesh):
    """Input batches: leading batch dim over the composite data axes."""
    da = batch_axes(mesh)
    da = da if len(da) > 1 else da[0]

    def one(leaf):
        spec = _guard(mesh, (da,) + (None,) * (len(leaf.shape) - 1), leaf.shape)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(one, batch_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
