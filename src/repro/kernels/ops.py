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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_diff(q, k, v, causal, scale, block_q, block_k):
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k)


def _attention_fwd(q, k, v, causal, scale, block_q, block_k):
    return _attention_diff(q, k, v, causal, scale, block_q, block_k), (q, k, v)


def _attention_bwd(causal, scale, block_q, block_k, res, do):
    # backward via the oracle's VJP (recompute-style; the Pallas backward
    # kernel is a further optimization tracked in EXPERIMENTS.md §Perf)
    q, k, v = res
    _, pullback = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal,
                                         scale=scale), q, k, v)
    return pullback(do)


_attention_diff.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, *, causal: bool = True, scale=None,
              use_pallas: bool = True, block_q: int = 128, block_k: int = 128):
    if use_pallas:
        return _attention_diff(q, k, v, causal, scale, block_q, block_k)
    return ref.attention(q, k, v, causal=causal, scale=scale)


def decode_attention(q, k_cache, v_cache, *, kv_len=None, scale=None,
                     use_pallas: bool = True, block_k: int = 512):
    import numpy as _np
    dynamic = kv_len is not None and not isinstance(kv_len, (int, _np.integer))
    if dynamic:
        # traced per-call length (continuous-batching serving): mask path
        lengths = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32),
                                   (q.shape[0],))
        return ref.decode_attention(q, k_cache, v_cache, lengths=lengths,
                                    scale=scale)
    if use_pallas:
        return _flash_decode(q, k_cache, v_cache, kv_len=kv_len, scale=scale,
                             block_k=block_k)
    if kv_len is not None and kv_len < k_cache.shape[2]:
        k_cache = k_cache[:, :, :kv_len, :]
        v_cache = v_cache[:, :, :kv_len, :]
    return ref.decode_attention(q, k_cache, v_cache, scale=scale)


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
