"""Decode attention in the serving wave: one length-bound ``flash_decode``
kernel per attention layer over all of the wave's slots, the same tokens
as the jnp mask path, and the wave's live share of the cache.

The structure is read off the jaxpr (on the CPU a Pallas kernel lowers
to the interpreter's loops, so the HLO cannot show it): a kernel is a
``pallas_call`` named ``flash_decode``; Pallas's own batching of a
batched length operand would be a loop (``scan`` or ``while``) around a
``pallas_call``;
the mask path is a ``dot_general`` with an operand as long as the cache.
"""
import contextlib

import jax
import jax.numpy as jnp
import jax._src.core as jcore
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models import build_model
from repro.runtime import spans
from repro.serving import ContinuousBatcher

#: a cache length no width of the reduced models shares
MAX_LEN = 40


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _eqns(jaxpr):
    """Every equation, inner jaxprs included, but not a kernel's body."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name != "pallas_call":
            for sub in _subjaxprs(e):
                yield from _eqns(sub)


def _structure(closed, cache_len: int) -> tuple[int, int, int]:
    """(``flash_decode`` kernels, loops around a kernel, dots over the
    whole cache) in ``closed``."""
    eqns = list(_eqns(closed.jaxpr))
    kernels = sum(e.primitive.name == "pallas_call"
                  and e.params["name"] == "flash_decode" for e in eqns)
    loops = sum(e.primitive.name in ("scan", "while")
                and any(x.primitive.name == "pallas_call"
                        for sub in _subjaxprs(e) for x in _eqns(sub))
                for e in eqns)
    full = sum(e.primitive.name == "dot_general"
               and any(cache_len in v.aval.shape for v in e.invars)
               for e in eqns)
    return kernels, loops, full


def _wave_args(n, hkv, group, S, D):
    return (jnp.zeros((n, 1, hkv * group, D)),
            jnp.zeros((n, 1, hkv, S, D)), jnp.zeros((n, 1, hkv, S, D)),
            jnp.zeros((n,), jnp.int32))


def test_vmapped_decode_attention_is_one_kernel():
    """A traced per-slot ``kv_len`` under ``vmap`` is one kernel over the
    folded slots; the mask path's dots and Pallas's loop batching (a
    ``vmap`` of the bare kernel over batched lengths) are what the
    check would see."""
    args = _wave_args(3, 2, 4, MAX_LEN, 16)

    def wave(use_pallas):
        return jax.vmap(lambda q, k, v, p: ops.decode_attention(
            q, k, v, kv_len=p + 1, use_pallas=use_pallas))

    assert _structure(jax.make_jaxpr(wave(True))(*args), MAX_LEN) \
        == (1, 0, 0)
    assert _structure(jax.make_jaxpr(wave(False))(*args), MAX_LEN) \
        == (0, 0, 2)
    bare = jax.vmap(lambda q, k, v, p: fa.flash_decode(q, k, v, p + 1))
    kernels, loops, _ = _structure(jax.make_jaxpr(bare)(*args), MAX_LEN)
    assert kernels == 1 and loops == 1


def _batcher(arch, fusion, stitched=None):
    cfg = get_config(arch).reduced()
    mdl = build_model(cfg, fusion_mode=fusion)
    params = mdl.init(jax.random.PRNGKey(0))
    return cfg, ContinuousBatcher(mdl, params, n_slots=2, max_len=MAX_LEN,
                                  stitched=stitched)


#: attention layers of the reduced models: SmallThinker "ASSS" (one
#: global, three rings), zamba2 the shared block before layers 0 and 2
ATTENTION = {"smallthinker-21b-a3b": 4, "zamba2-1.2b": 2}


@pytest.mark.parametrize("arch", sorted(ATTENTION))
@pytest.mark.parametrize("stitched", [True, False],
                         ids=["stitched", "jit"])
def test_decode_wave_holds_one_kernel_per_attention_layer(arch, stitched):
    """The wave program, stitched or plainly jitted, runs one
    ``flash_decode`` per attention layer over both slots, with no loop
    of kernels and no dot over the whole cache; ``--fusion xla`` keeps
    the mask path."""
    _, cb = _batcher(arch, "stitched", stitched=stitched)
    args = (cb.params, cb.cache, jnp.zeros((2, 1, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32))
    if stitched:
        c = cb._decode_wave.compiled(*args)
        closed = jax.make_jaxpr(c._jitted)(*jax.tree_util.tree_leaves(args))
    else:
        closed = jax.make_jaxpr(cb._decode_wave)(*args)
    assert _structure(closed, MAX_LEN) == (ATTENTION[arch], 0, 0)

    _, xcb = _batcher(arch, "xla")
    kernels, _, full = _structure(jax.make_jaxpr(xcb._decode_wave)(*args),
                                  MAX_LEN)
    assert kernels == 0 and full > 0


def _serve(cb, cfg, lens_gens):
    r = np.random.default_rng(4)
    prompts = [r.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in lens_gens]
    rids = [cb.submit(p, max_new=g) for p, (_, g) in zip(prompts, lens_gens)]
    out = cb.run()
    return [out[i] for i in rids]


def test_windowed_serving_matches_the_mask_path():
    """A windowed model (rings of 8) serves the same tokens with the
    kernel as with the mask path.  The first request's ring wraps in
    its prefill and again in decode, the second's in decode; the third
    reuses the first's slot after it, shorter than the first grew."""
    lens_gens = [(12, 4), (3, 16), (2, 12)]
    cfg, kern = _batcher("smallthinker-21b-a3b", "stitched", stitched=False)
    _, mask = _batcher("smallthinker-21b-a3b", "xla")
    assert cfg.sliding_window == 8
    got = _serve(kern, cfg, lens_gens)
    assert got == _serve(mask, cfg, lens_gens)
    assert [len(g) for g in got] == [g for _, g in lens_gens]


class Recorder:
    """Records ``(name, meta)`` of every span."""

    def __init__(self):
        self.seen: list = []

    @contextlib.contextmanager
    def __call__(self, name, **meta):
        self.seen.append((name, meta))
        yield


def test_kv_live_is_the_held_slots_live_share(monkeypatch):
    """``serve.wave`` carries ``kv_live``: the held slots' positions read
    (``pos + 1`` each) over ``n_active * max_len``; ``ServeStats`` sums
    it and reads out its mean over waves."""
    cfg, cb = _batcher("llama3.2-3b", "xla")
    rec = Recorder()
    monkeypatch.setattr(spans, "span", rec)
    r = np.random.default_rng(5)
    for n in (5, 7):
        cb.submit(r.integers(0, cfg.vocab_size, n).astype(np.int32),
                  max_new=3)
    cb._fill_slots()
    cb._decode_step()                  # positions 5 and 7, the next ahead
    cb._decode_step()                  # positions 6 and 8, read back
    waves = [m for n, m in rec.seen if n == "serve.wave"]
    want = [(6 + 8) / (2 * MAX_LEN), (7 + 9) / (2 * MAX_LEN)]
    assert waves == [{"n_active": 2, "kv_live": w} for w in want]
    assert cb.stats.kv_live == pytest.approx(sum(want) / 2)
    assert f"kv live {sum(want) / 2:.1%}" in cb.stats.summary()
