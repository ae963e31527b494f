"""Public wrappers over the Pallas kernels with oracle fallback.

``use_pallas=False`` (or ``fusion_mode="xla"`` at the model level) routes
to the pure-jnp oracles in ``ref.py`` -- the XLA-baseline execution mode.
With ``use_pallas=True`` every kernel compiles to Mosaic on a TPU backend
and runs in the Pallas interpreter anywhere else
(``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash_attention
from .flash_attention import flash_attention_window as _flash_window
from .flash_attention import flash_decode as _flash_decode
from .layernorm import layernorm as _layernorm_kernel
from .rmsnorm import rmsnorm as _rmsnorm_kernel
from .softmax import softmax as _softmax_kernel
from .ssd_scan import ssd_scan as _ssd_scan_kernel


def layernorm(x, gamma, beta, eps: float = 1e-6, *, use_pallas: bool = True):
    if use_pallas:
        return _layernorm_kernel(x, gamma, beta, eps)
    return ref.layernorm(x, gamma, beta, eps)


def rmsnorm(x, gamma, eps: float = 1e-6, *, use_pallas: bool = True):
    if use_pallas:
        return _rmsnorm_kernel(x, gamma, eps)
    return ref.rmsnorm(x, gamma, eps)


def softmax(x, *, use_pallas: bool = True):
    if use_pallas:
        return _softmax_kernel(x)
    return ref.softmax(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention_diff(q, k, v, causal, scale, block_q, block_k, window):
    if window:
        return _flash_window(q, k, v, window=window, scale=scale,
                             block_q=block_q, block_k=block_k)
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k)


def _attention_fwd(q, k, v, causal, scale, block_q, block_k, window):
    return (_attention_diff(q, k, v, causal, scale, block_q, block_k,
                            window), (q, k, v))


def _attention_bwd(causal, scale, block_q, block_k, window, res, do):
    # backward via the oracle's VJP (recompute-style; the Pallas backward
    # kernel is a further optimization tracked in EXPERIMENTS.md §Perf)
    q, k, v = res
    _, pullback = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal,
                                         scale=scale, window=window),
        q, k, v)
    return pullback(do)


_attention_diff.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, *, causal: bool = True, scale=None,
              use_pallas: bool = True, block_q: int = 128, block_k: int = 128,
              window: int = 0):
    """``window`` > 0: causal attention over the last ``window``
    positions (``flash_attention_window``)."""
    assert causal or not window, "a window is causal"
    if use_pallas:
        return _attention_diff(q, k, v, causal, scale, block_q, block_k,
                               window)
    return ref.attention(q, k, v, causal=causal, scale=scale, window=window)


@functools.lru_cache(maxsize=None)
def _decode_by_length(scale, block_k):
    """``flash_decode`` over per-row lengths, batched by folding: under
    ``vmap`` the mapped axis joins the kernel's own batch axis (caches
    ``[N, B, Hkv, S, D]`` run as ``[N*B, Hkv, S, D]``, lengths as
    ``[N*B]``), so a wave of slots is one kernel, not Pallas's loop of
    one kernel per slot over a batched scalar-prefetch operand."""

    @jax.custom_batching.custom_vmap
    def decode(q, k_cache, v_cache, lengths):
        return _flash_decode(q, k_cache, v_cache, lengths, scale=scale,
                             block_k=block_k)

    @decode.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = decode(*(a.reshape((-1,) + a.shape[2:]) for a in args))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return decode


def decode_attention(q, k_cache, v_cache, *, kv_len=None, scale=None,
                     use_pallas: bool = True, block_k: int | None = None):
    """q [B, Hq, D] against caches [B, Hkv, S, D] over the first
    ``kv_len`` positions (an int, a traced scalar or ``[B]`` per row;
    ``None``: all ``S``).  With ``use_pallas`` the kernel reads only the
    key blocks below each row's length; else the oracle masks scores
    over the whole cache."""
    B, S = q.shape[0], k_cache.shape[2]
    lengths = jnp.broadcast_to(
        jnp.asarray(S if kv_len is None else kv_len, jnp.int32), (B,))
    if use_pallas:
        return _decode_by_length(scale, block_k)(q, k_cache, v_cache,
                                                 lengths)
    return ref.decode_attention(q, k_cache, v_cache, lengths=lengths,
                                scale=scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_diff(x, dt, A, B, C, chunk):
    return _ssd_scan_kernel(x, dt, A, B, C, chunk=chunk)


def _ssd_fwd(x, dt, A, B, C, chunk):
    return _ssd_diff(x, dt, A, B, C, chunk), (x, dt, A, B, C)


def _ssd_bwd(chunk, res, cts):
    x, dt, A, B, C = res
    _, pullback = jax.vjp(
        lambda *a: ref.ssd_scan(*a, chunk=chunk), x, dt, A, B, C)
    return pullback(cts)


_ssd_diff.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, use_pallas: bool = True):
    if use_pallas:
        return _ssd_diff(x, dt, A, B, C, chunk)
    return ref.ssd_scan(x, dt, A, B, C, chunk=chunk)
