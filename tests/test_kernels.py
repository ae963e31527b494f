"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref

rng = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" \
        else dict(rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 64), (3, 128), (2, 7, 256), (1, 1024),
                                   (5, 130)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layernorm_sweep(shape, dtype):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    g = jnp.asarray(rng.standard_normal(shape[-1]), dtype)
    b = jnp.asarray(rng.standard_normal(shape[-1]), dtype)
    out = ops.layernorm(x, g, b)
    want = ref.layernorm(x, g, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", [(4, 64), (2, 3, 96), (1, 2048)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rmsnorm_sweep(shape, dtype):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    g = jnp.asarray(rng.standard_normal(shape[-1]), dtype)
    np.testing.assert_allclose(
        np.asarray(ops.rmsnorm(x, g), np.float32),
        np.asarray(ref.rmsnorm(x, g), np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", [(8, 32), (2, 4, 333), (1, 4096)])
def test_softmax_sweep(shape):
    x = jnp.asarray(rng.standard_normal(shape) * 4, jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.softmax(x)),
                               np.asarray(ref.softmax(x)),
                               rtol=1e-5, atol=1e-6)


def test_norm_grads_match_oracle():
    x = rng.standard_normal((6, 80)).astype(np.float32)
    g = rng.standard_normal(80).astype(np.float32)
    b = rng.standard_normal(80).astype(np.float32)
    f_k = lambda *a: jnp.sum(jnp.sin(ops.layernorm(*a)))
    f_r = lambda *a: jnp.sum(jnp.sin(ref.layernorm(*a)))
    gk = jax.grad(f_k, (0, 1, 2))(x, g, b)
    gr = jax.grad(f_r, (0, 1, 2))(x, g, b)
    for a, bb in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("hq,hkv,sq,skv,bq,bk", [
    (4, 4, 64, 64, 16, 16),       # MHA square
    (8, 2, 100, 100, 32, 32),     # GQA, unaligned seq
    (4, 1, 16, 80, 8, 16),        # MQA cross (prefill continuation)
    (6, 2, 33, 33, 64, 64),       # block > seq
])
def test_flash_attention_sweep(hq, hkv, sq, skv, bq, bk):
    q = rng.standard_normal((2, hq, sq, 32)).astype(np.float32)
    k = rng.standard_normal((2, hkv, skv, 32)).astype(np.float32)
    v = rng.standard_normal((2, hkv, skv, 32)).astype(np.float32)
    out = ops.attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_noncausal_and_bf16():
    q = jnp.asarray(rng.standard_normal((1, 4, 48, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 4, 48, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 4, 48, 64)), jnp.bfloat16)
    out = ops.attention(q, k, v, causal=False, block_q=16, block_k=16)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_attention_grads():
    q = rng.standard_normal((1, 2, 32, 16)).astype(np.float32)
    k = rng.standard_normal((1, 2, 32, 16)).astype(np.float32)
    v = rng.standard_normal((1, 2, 32, 16)).astype(np.float32)
    f_k = lambda *a: jnp.sum(ops.attention(*a, causal=True) ** 2)
    f_r = lambda *a: jnp.sum(ref.attention(*a, causal=True) ** 2)
    gk = jax.grad(f_k, (0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def _qkv(hq, hkv, sq, skv, d=32, b=2):
    return (rng.standard_normal((b, hq, sq, d)).astype(np.float32),
            rng.standard_normal((b, hkv, skv, d)).astype(np.float32),
            rng.standard_normal((b, hkv, skv, d)).astype(np.float32))


@pytest.mark.parametrize("hq,hkv,sq,skv,window,bq,bk", [
    (4, 2, 64, 64, 16, 16, 16),     # window a multiple of the block
    (4, 2, 100, 100, 20, 32, 16),   # window off the block grid, seq too
    (8, 2, 96, 96, 40, 16, 32),     # key blocks wider than query blocks
    (2, 1, 48, 48, 64, 16, 16),     # window >= seq: plain causal
    (2, 1, 48, 48, 48, 16, 16),     # window == seq
    (4, 1, 16, 80, 24, 8, 16),      # queries at the end of the keys
    (2, 2, 33, 33, 1, 64, 64),      # each query sees itself only
])
def test_flash_attention_window_sweep(hq, hkv, sq, skv, window, bq, bk):
    q, k, v = _qkv(hq, hkv, sq, skv)
    out = ops.attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                        window=window)
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_window_reference_matches_a_loop():
    """The oracle's window mask against a query-by-query loop over the
    keys ``i + Skv - Sq - window < j <= i + Skv - Sq``."""
    q, k, v = _qkv(2, 1, 12, 20, d=8, b=1)
    window = 5
    got = np.asarray(ref.attention(q, k, v, causal=True, window=window))
    for h in range(2):
        for i in range(12):
            at = i + 20 - 12
            js = np.arange(max(0, at - window + 1), at + 1)
            s = k[0, 0, js] @ q[0, h, i] / np.sqrt(8)
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ v[0, 0, js]
            np.testing.assert_allclose(got[0, h, i], want, rtol=1e-5,
                                       atol=1e-6)


def test_flash_attention_window_skips_blocks_outside():
    """A NaN in a key block wholly behind a query block's window does not
    reach it: that block is never read (a read block's masked NaN
    would, through ``p @ v``)."""
    q, k, v = _qkv(2, 1, 128, 128, d=16, b=1)
    v[0, 0, 5] = np.nan                   # key block 0 of 16-blocks
    out = np.asarray(ops.attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, window=16))
    # query block 2 (32-47) sees keys from 17: blocks 1-2
    assert np.isnan(out[0, :, 5:32]).any()
    assert np.isfinite(out[0, :, 32:]).all()
    v[0, 0, 5] = 0.0
    want = ref.attention(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(out[0, :, 32:], np.asarray(want)[0, :, 32:],
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_window_grads():
    q, k, v = _qkv(2, 1, 40, 40, d=16, b=1)
    f_k = lambda *a: jnp.sum(ops.attention(*a, causal=True, block_q=16,
                                           block_k=16, window=12) ** 2)
    f_r = lambda *a: jnp.sum(ref.attention(*a, causal=True, window=12) ** 2)
    gk = jax.grad(f_k, (0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("kvlen", [96, 64, 40])
def test_flash_decode(kvlen):
    q = rng.standard_normal((2, 8, 64)).astype(np.float32)
    kc = rng.standard_normal((2, 2, 96, 64)).astype(np.float32)
    vc = rng.standard_normal((2, 2, 96, 64)).astype(np.float32)
    out = ops.decode_attention(q, kc, vc, kv_len=kvlen, block_k=32)
    want = ops.decode_attention(q, kc, vc, kv_len=kvlen, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _edge_lengths(blk, S):
    """A row at each edge of a key block: 1, blk - 1, blk, blk + 1, S."""
    return np.array([1, blk - 1, blk, blk + 1, S], np.int32)


def _decode_case(group, hkv, S, D, lengths):
    """q and caches for ``len(lengths)`` rows; past each row's length the
    cache holds large finite keys and values, as a reused slot may."""
    B = len(lengths)
    q = rng.standard_normal((B, hkv * group, D)).astype(np.float32)
    k = rng.standard_normal((B, hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((B, hkv, S, D)).astype(np.float32)
    for b, n in enumerate(lengths):
        k[b, :, n:] = 1e4
        v[b, :, n:] = -1e4
    return q, k, v


@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("heads_per_step", [1, 2])
def test_flash_decode_lengths(group, heads_per_step, monkeypatch):
    """Per-row lengths at each edge of a key block against the oracle's
    mask, with one or two kv heads (and their query groups) per step:
    nothing past a row's length reaches its result."""
    hkv, S, D, blk = 2, 64, 32, 16
    # a step holds up to twice the aim: K and V of ``heads_per_step`` heads
    monkeypatch.setattr(fa, "DECODE_STEP_BYTES", heads_per_step * blk * D * 4)
    assert fa._decode_blocks(hkv, S, D, 4, blk) == (blk, heads_per_step)
    lengths = _edge_lengths(blk, S)
    q, k, v = _decode_case(group, hkv, S, D, lengths)
    out = fa.flash_decode(q, k, v, lengths, block_k=blk)
    want = ref.decode_attention(q, k, v, lengths=jnp.asarray(lengths))
    assert np.abs(np.asarray(want)).max() < 10
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("group", [1, 7])
def test_decode_attention_vmapped_per_slot_lengths(group):
    """The decode wave's layout: ``vmap`` over slots of one-row caches
    ``[N, 1, Hkv, S, D]`` with a traced ``kv_len = pos + 1`` per slot,
    folded into one kernel; a second ``vmap`` over two waves folds
    too."""
    hkv, S, D, blk = 2, 48, 16, 16
    pos = _edge_lengths(blk, S) - 1
    q, k, v = (x[:, None] for x in _decode_case(group, hkv, S, D, pos + 1))

    def wave(use_pallas):
        return jax.vmap(lambda q, k, v, p: ops.decode_attention(
            q, k, v, kv_len=p + 1, block_k=blk, use_pallas=use_pallas))

    want = np.asarray(wave(False)(q, k, v, pos))
    out = jax.jit(wave(True))(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)
    two = jax.vmap(wave(True))(*(jnp.stack([x[::-1], x])
                                 for x in (q, k, v, pos)))
    np.testing.assert_allclose(np.asarray(two[1]), want,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(two[0]), want[::-1],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("L,H,P,N,chunk", [
    (64, 2, 16, 16, 16), (128, 4, 32, 64, 32), (96, 1, 64, 128, 48),
])
def test_ssd_scan_sweep(L, H, P, N, chunk):
    b = 2
    x = (rng.standard_normal((b, L, H, P)) * 0.5).astype(np.float32)
    dt = (np.abs(rng.standard_normal((b, L, H))) * 0.1 + 0.01).astype(np.float32)
    A = -np.abs(rng.standard_normal(H)).astype(np.float32)
    B = (rng.standard_normal((b, L, N)) * 0.5).astype(np.float32)
    C = (rng.standard_normal((b, L, N)) * 0.5).astype(np.float32)
    y1, s1 = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    y2, s2 = ref.ssd_scan(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunk_invariance():
    """SSD output must not depend on the chunking (algebraic identity)."""
    b, L, H, P, N = 1, 128, 2, 16, 32
    x = (rng.standard_normal((b, L, H, P)) * 0.5).astype(np.float32)
    dt = (np.abs(rng.standard_normal((b, L, H))) * 0.1 + 0.01).astype(np.float32)
    A = -np.abs(rng.standard_normal(H)).astype(np.float32)
    B = (rng.standard_normal((b, L, N)) * 0.5).astype(np.float32)
    C = (rng.standard_normal((b, L, N)) * 0.5).astype(np.float32)
    y32, s32 = ref.ssd_scan(x, dt, A, B, C, chunk=32)
    y64, s64 = ref.ssd_scan(x, dt, A, B, C, chunk=64)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y64),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s32), np.asarray(s64),
                               rtol=1e-4, atol=1e-4)


def test_ssd_matches_naive_recurrence():
    """Chunked SSD == the literal h_t = h_{t-1} e^{dt A} + dt B x recurrence."""
    b, L, H, P, N = 1, 32, 2, 8, 8
    x = (rng.standard_normal((b, L, H, P)) * 0.5).astype(np.float32)
    dt = (np.abs(rng.standard_normal((b, L, H))) * 0.1 + 0.01).astype(np.float32)
    A = -np.abs(rng.standard_normal(H)).astype(np.float32)
    B = (rng.standard_normal((b, L, N)) * 0.5).astype(np.float32)
    C = (rng.standard_normal((b, L, N)) * 0.5).astype(np.float32)

    h = np.zeros((b, H, P, N), np.float32)
    ys = np.zeros((b, L, H, P), np.float32)
    for t in range(L):
        decay = np.exp(dt[:, t] * A[None, :])           # [b,H]
        upd = np.einsum("bh,bn,bhp->bhpn", dt[:, t], B[:, t], x[:, t])
        h = h * decay[..., None, None] + upd
        ys[:, t] = np.einsum("bn,bhpn->bhp", C[:, t], h)

    y, state = ref.ssd_scan(x, dt, A, B, C, chunk=8)
    np.testing.assert_allclose(np.asarray(y), ys, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), h, rtol=1e-4, atol=1e-4)
