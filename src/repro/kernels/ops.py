"""Jit'd public wrappers for the Pallas kernels in this package."""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import mpgemm as _mpgemm
from repro.kernels import ref as _ref

_log = logging.getLogger(__name__)
_FALLBACKS_LOGGED = set()


def _note_fallback(op: str, reason: str) -> None:
    """Log the FIRST implicit reference fallback per op; later ones are
    silent (the wrapper is jit'd — this fires at trace time, so a hot loop
    never spams the log).  Every fallback lands in the registry under its
    reason string, so the rate stays observable after the log goes quiet."""
    obs.counter_inc("kernel_fallback_total",
                    help="implicit XLA-reference fallbacks by reason",
                    op=op, reason=reason)
    if op not in _FALLBACKS_LOGGED:
        _FALLBACKS_LOGGED.add(op)
        _log.warning(
            "%s: tracing the XLA reference path instead of the Pallas "
            "kernel (%s)", op, reason)


def flash_attention_fallback_reason(
    q_dtype, k_dtype, v_dtype, *, backend: str,
) -> Optional[str]:
    """Why :func:`flash_attention` will trace the XLA reference instead of
    the Pallas kernel — None means the kernel path is taken.

    The predicate is deliberately public: callers (and tests) can ask it
    BEFORE tracing, and the wrapper's dispatch uses exactly this function,
    so the answer can never drift from the behavior.
    """
    if backend == "xla":
        return "backend='xla' requested"
    for name, dt in (("q", q_dtype), ("k", k_dtype), ("v", v_dtype)):
        if not jnp.issubdtype(jnp.dtype(dt), jnp.floating):
            return (f"non-float {name} dtype {jnp.dtype(dt).name} "
                    "(the kernel's online softmax needs float operands)")
    return None


@functools.partial(
    jax.jit,
    static_argnames=(
        "trans_a", "trans_b", "alpha", "beta", "activation", "out_dtype",
        "interpret", "backend",
    ),
)
def mpgemm(
    a,
    b,
    c=None,
    *,
    trans_a: bool = False,
    trans_b: bool = False,
    alpha: float = 1.0,
    beta: float = 0.0,
    bias=None,
    scale=None,
    activation: Optional[str] = None,
    out_dtype=None,
    interpret: bool = False,
    backend: str = "pallas",
):
    """out = activation(alpha * op(a)·op(b) * scale + bias) + beta*c."""
    if backend == "xla":
        return _ref.mpgemm_ref(
            a, b, c, trans_a=trans_a, trans_b=trans_b, alpha=alpha, beta=beta,
            bias=bias, scale=scale, activation=activation, out_dtype=out_dtype,
        )
    return _mpgemm.mpgemm_pallas(
        a, b, c, trans_a=trans_a, trans_b=trans_b, alpha=alpha, beta=beta,
        bias=bias, scale=scale, activation=activation, out_dtype=out_dtype,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret", "backend"),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window=None,
    scale=None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    backend: str = "pallas",
):
    """Blocked online-softmax attention; q (B,H,Tq,D), k/v (B,Hkv,Tk,D).

    Dispatch is explicit: :func:`flash_attention_fallback_reason` decides
    whether this call traces the Pallas kernel or the XLA reference.  An
    IMPLICIT fallback (anything other than ``backend="xla"``) is an error
    on the compiled TPU path, where it would hide the device behind the
    reference; under ``interpret=True`` it is logged once per process.
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"GQA requires H % Hkv == 0, got {q.shape[1]} % {k.shape[1]}")
    reason = flash_attention_fallback_reason(
        q.dtype, k.dtype, v.dtype, backend=backend)
    if reason is not None:
        if backend != "xla":
            if not interpret:
                raise ValueError(
                    f"flash_attention: the Pallas TPU kernel cannot run "
                    f"({reason}); pass backend='xla' for the reference")
            _note_fallback("flash_attention", reason)
        kr = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        vr = jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)
        return _ref.flash_attention_ref(q, kr, vr, causal=causal,
                                        window=window, scale=scale)
    from repro.kernels.flash_attention import flash_attention as _fa
    return _fa(q, k, v, causal=causal, window=window, scale=scale,
               block_q=block_q, block_k=block_k, interpret=interpret)
