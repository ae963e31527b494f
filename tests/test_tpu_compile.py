"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret mode cannot see what the chip's compiler (Mosaic) refuses:
blocks whose trailing dimensions are not tile-aligned, or more VMEM
than a kernel may use.  These tests lower each kernel at zamba2-1.2b
widths for a ``v5e:2x2`` topology that is described, not attached, and
check that a Mosaic kernel (``tpu_custom_call``) is in the program.
Nothing runs; no chip is needed.

The topology is described inside a module fixture (never at import:
only one process may load the TPU library, and every test worker
imports this file), and the compiles stay in the test's own process.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.core.cost_model import Hardware
from repro.core.stitch import stitched_jit
from repro.kernels import ops
from repro.kernels.flash_attention import (flash_attention,
                                          flash_attention_window,
                                          flash_decode)

#: zamba2-1.2b widths (configs/zamba2_1_2b.py)
D_MODEL, HEADS, HEAD_DIM, D_FF = 2048, 32, 64, 8192
SSM_HEADS, SSM_HEAD_DIM, SSM_STATE, SSM_CHUNK = 64, 64, 64, 64

DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32", "bf16"])


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def spec(one_chip, monkeypatch):
    """Shape builder on the described chip.  The host backend is the
    CPU, so kernels are steered to compile (not interpret) here."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _kernels_in(lowered) -> int:
    return lowered.compile().as_text().count("tpu_custom_call")


def _compile_stitched(fn, args, **kw):
    """Plan ``fn`` with ``stitched_jit`` and compile its program for the
    chip; return (emitted schedules, tpu_custom_call count)."""
    c = stitched_jit(fn, **kw).compiled(*args)
    rep = c.report
    assert not rep.fallbacks and rep.rung in ("stitched", "anchored"), \
        (rep.fallbacks, rep.rung)
    sched = [e.estimate.schedule for e in c.emitted if e.kind == "pallas"]
    return sched, _kernels_in(c._jitted.lower(*args))


def _residual_rmsnorm_gelu(x, r, g):
    h = x + r
    ms = jnp.mean(h.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    y = (h * jax.lax.rsqrt(ms + 1e-6) * g).astype(x.dtype)
    return jax.nn.gelu(y, approximate=True), h


@DTYPES
@pytest.mark.parametrize("schedule", ["onepass", "streaming"])
def test_stitched_chain_compiles(spec, dtype, schedule):
    args = (spec((2048, D_MODEL), dtype), spec((2048, D_MODEL), dtype),
            spec((D_MODEL,), dtype))
    # a VMEM budget too small for whole rows makes the planner stream
    # the row in column tiles; the kernel still compiles for the chip
    hw = Hardware() if schedule == "onepass" else \
        Hardware(vmem_bytes=512 * 1024)
    sched, n = _compile_stitched(_residual_rmsnorm_gelu, args, hw=hw)
    assert sched and set(sched) == {schedule}, sched
    assert n >= 1


def test_names_survive_a_v5e_compile(spec):
    """The stitched program's module, a stitch group's kernel and a hand
    kernel keep their names through the chip's compiler: the names the
    profiler's trace shows."""
    args = (spec((2048, D_MODEL), jnp.float32),
            spec((2048, D_MODEL), jnp.float32), spec((D_MODEL,), jnp.float32))
    c = stitched_jit(_residual_rmsnorm_gelu).compiled(*args)
    text = c._jitted.lower(*args).compile().as_text()
    assert "HloModule jit_stitched__residual_rmsnorm_gelu" in text
    assert re.search(r"%stitch_onepass_0(\.\d+)? = .*tpu_custom_call", text)
    hand = jax.jit(lambda x, g: ops.rmsnorm(x, g)).lower(
        spec((4, 128, D_MODEL), jnp.float32),
        spec((D_MODEL,), jnp.float32)).compile().as_text()
    assert re.search(r"%rmsnorm(\.\d+)? = .*tpu_custom_call", hand)


@DTYPES
def test_rmsnorm_compiles(spec, dtype):
    f = jax.jit(lambda x, g: ops.rmsnorm(x, g))
    assert _kernels_in(f.lower(spec((4, 128, D_MODEL), dtype),
                               spec((D_MODEL,), dtype))) >= 1


@DTYPES
def test_rmsnorm_compiles_at_granite_width(spec, dtype):
    """The gated norm of a 1024-token granite-4.0-h-small prefill: rows
    of d_inner 8192, whose 128-row blocks would overrun scoped VMEM."""
    f = jax.jit(lambda x, g: ops.rmsnorm(x, g))
    assert _kernels_in(f.lower(spec((1, 1024, 8192), dtype),
                               spec((8192,), dtype))) >= 1


def test_gqa_attention_compiles_at_granite_width(spec):
    """granite-4.0-h-small's prefill attention: 32 query heads over 8
    key/value heads of 128, the configured 1/128 scale."""
    B, S = 1, 1024
    f = jax.jit(functools.partial(flash_attention, causal=True,
                                  scale=1 / 128))
    lowered = f.lower(spec((B, 32, S, 128), jnp.float32),
                      spec((B, 8, S, 128), jnp.float32),
                      spec((B, 8, S, 128), jnp.float32))
    assert _kernels_in(lowered) >= 1


@pytest.mark.parametrize("S", [1024, 8192])
def test_window_attention_compiles_at_smallthinker_width(spec, S):
    """smallthinker-21b-a3b's windowed prefill attention: 28 query heads
    over 4 key/value heads of 128, a 4096-position window, a prompt
    inside it and one twice its length; the kernel keeps its name."""
    f = jax.jit(functools.partial(flash_attention_window, window=4096))
    text = f.lower(spec((1, 28, S, 128), jnp.float32),
                   spec((1, 4, S, 128), jnp.float32),
                   spec((1, 4, S, 128), jnp.float32)).compile().as_text()
    assert re.search(r"%flash_attention_window(\.\d+)? = .*tpu_custom_call",
                     text)


@DTYPES
def test_ssd_scan_compiles(spec, dtype):
    b, L = 4, 128
    f = jax.jit(functools.partial(ops.ssd_scan, chunk=SSM_CHUNK))
    lowered = f.lower(spec((b, L, SSM_HEADS, SSM_HEAD_DIM), dtype),
                      spec((b, L, SSM_HEADS), jnp.float32),
                      spec((SSM_HEADS,), jnp.float32),
                      spec((b, L, SSM_STATE), dtype),
                      spec((b, L, SSM_STATE), dtype))
    assert _kernels_in(lowered) >= 1


@DTYPES
def test_flash_attention_score_mod_compiles(spec, dtype):
    B, S = 2, 512

    def attn(q, k, v, bias):
        return flash_attention(q, k, v, causal=True,
                               score_mod=lambda s, b: s + b,
                               score_args=(bias,))
    lowered = jax.jit(attn).lower(
        *(spec((B, HEADS, S, HEAD_DIM), dtype) for _ in range(3)),
        spec((1, 1, S, S), jnp.float32))
    assert _kernels_in(lowered) >= 1


#: decode caches (slots, kv heads, positions, query group) of the cells:
#: SmallThinker's global layers and rings, zamba2's shared block
DECODE_SHAPES = pytest.mark.parametrize("B,hkv,S,group", [
    (24, 4, 12288, 7), (24, 4, 4096, 7), (16, 32, 1024, 1)],
    ids=["smallthinker-global", "smallthinker-ring", "zamba2"])


@DTYPES
@DECODE_SHAPES
def test_flash_decode_compiles(spec, dtype, B, hkv, S, group):
    D = 128
    lowered = jax.jit(flash_decode).lower(
        spec((B, hkv * group, D), dtype), spec((B, hkv, S, D), dtype),
        spec((B, hkv, S, D), dtype), spec((B,), jnp.int32))
    assert _kernels_in(lowered) >= 1


def test_decode_wave_attention_compiles_to_one_kernel(spec):
    """The wave's layout (a ``vmap`` over slots of one-row caches with a
    traced length each) compiles to one kernel and no loop."""
    B, hkv, S, group, D = 24, 4, 12288, 7, 128
    wave = jax.vmap(lambda q, k, v, p: ops.decode_attention(
        q, k, v, kv_len=p + 1))
    text = jax.jit(wave).lower(
        spec((B, 1, hkv * group, D), jnp.float32),
        spec((B, 1, hkv, S, D), jnp.float32),
        spec((B, 1, hkv, S, D), jnp.float32),
        spec((B,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"\bwhile\b", text)


def _norm_matmul_gelu(x, g, w, r):
    ms = jnp.mean(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    y = (x * jax.lax.rsqrt(ms + 1e-6) * g).astype(x.dtype)
    return jax.nn.gelu(y @ w, approximate=True) + r


@pytest.mark.parametrize("dtype,n_out,anchored", [
    (jnp.float32, 1024, True),     # admitted: panel + tiles fit
    (jnp.float32, 2048, False),    # Mosaic refuses the fused kernel
    (jnp.float32, D_FF, False),
    (jnp.bfloat16, 2048, True),
    (jnp.bfloat16, 4096, False),
], ids=["f32-1024", "f32-2048", "f32-8192", "bf16-2048", "bf16-4096"])
def test_anchored_matmul_pricing_matches_compiler(spec, dtype, n_out,
                                                  anchored):
    """The anchor pricing admits a fold only where ``matmul_fused``
    compiles; a refused fold leaves an ordinary program that does."""
    M = 512
    args = (spec((M, D_MODEL), dtype), spec((D_MODEL,), dtype),
            spec((D_MODEL, n_out), dtype), spec((M, n_out), dtype))
    c = stitched_jit(_norm_matmul_gelu).compiled(*args)
    assert (c.report.n_anchored >= 1) is anchored
    assert not c.report.fallbacks
    assert _kernels_in(c._jitted.lower(*args)) >= 1
