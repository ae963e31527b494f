"""Spans, counters and names inside the serving and compile paths.

A tiny stitched batcher runs with ``spans.span`` swapped for a recorder:
one prefill and one decode wave emit the named spans, nested as the
trace reduction expects; a cold call builds under ``stitch.build``.
``Request.t_admit`` and ``ServeStats.queue_wait`` time the queue, the
plan's phases add up to no more than its time, and the compiled
programs and kernels carry stable names.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.stitch import StitchedFunction, stitched_jit
from repro.kernels import ops
from repro.models import build_model
from repro.runtime import spans
from repro.serving import ContinuousBatcher


class Recorder:
    """Records spans as a tree of ``[name, meta, children]``."""

    def __init__(self):
        self.roots: list = []
        self._stack: list = []

    @contextlib.contextmanager
    def __call__(self, name, **meta):
        node = [name, meta, []]
        (self._stack[-1][2] if self._stack else self.roots).append(node)
        self._stack.append(node)
        try:
            yield
        finally:
            self._stack.pop()

    def take(self) -> list:
        roots, self.roots = self.roots, []
        return roots


def shape(node) -> tuple:
    """``(name, (children...))`` of a recorded span."""
    name, _, kids = node
    return name, tuple(shape(k) for k in kids)


def walk(nodes):
    for n in nodes:
        yield n
        yield from walk(n[2])


CALL = ("stitch.call", (("stitch.lookup", ()), ("stitch.launch", ())))


@pytest.fixture(scope="module")
def batcher():
    cfg = get_config("llama3.2-3b").reduced()
    mdl = build_model(cfg, fusion_mode="xla")
    params = mdl.init(jax.random.PRNGKey(0))
    cb = ContinuousBatcher(mdl, params, n_slots=2, max_len=32,
                           stitched=True)
    return cfg, cb


def _prompt(cfg, n):
    return np.random.default_rng(n).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def test_prefill_and_wave_spans_nest_as_named(batcher, monkeypatch):
    cfg, cb = batcher
    rec = Recorder()
    monkeypatch.setattr(spans, "span", rec)
    # cold: the first call of each program builds inside its lookup
    cb.submit(_prompt(cfg, 6), max_new=4)
    req = cb.queue.popleft()
    cb._prefill_slot(0, req)
    cb.slots[0] = req
    cold = rec.take()
    lookup = cold[0][2][0][2][0]
    assert lookup[0] == "stitch.lookup"
    build = lookup[2][0]
    assert build[:2] == ["stitch.build", {"program": "stitched_prefill"}]
    assert [k[0] for k in build[2]] == ["stitch.trace", "stitch.search",
                                       "stitch.search", "stitch.emit"]
    cb._decode_step()
    rec.take()

    # warm: a second request's prefill, then one wave over both slots;
    # neither stops at it, so the next wave is dispatched ahead
    cb.submit(_prompt(cfg, 7), max_new=4)   # the same bucket: warm
    req = cb.queue.popleft()
    cb._prefill_slot(1, req)
    cb.slots[1] = req
    cb._decode_step()
    prefill, wave = rec.take()
    assert shape(prefill) == ("serve.prefill", (
        CALL, ("prefill.cache_write", ()), ("prefill.sample", ())))
    assert prefill[1] == {"rid": req.rid, "slot": 1, "plen": 7,
                          "wrapped": False}
    leaves = {"leaves": len(jax.tree_util.tree_leaves(cb.cache))}
    assert prefill[2][1][1] == leaves
    assert prefill[2][0][1] == {"program": "stitched_prefill"}
    assert shape(wave) == ("serve.wave", (
        ("wave.inputs", ()), CALL, ("wave.ahead", (CALL,)),
        ("wave.sample", ()), ("wave.retire", ())))
    # both slots read 8 positions of 32 (kv_live)
    assert wave[1] == {"n_active": 2, "kv_live": 16 / 64}
    assert wave[2][1][1] == {"program": "stitched_decode_wave"}
    assert wave[2][2][2][0][1] == {"program": "stitched_decode_wave"}

    # the wave in flight is only read back: slot 0 stops at it
    cb._decode_step()
    (wave,) = rec.take()
    assert shape(wave) == ("serve.wave", (
        ("wave.sample", ()), ("wave.retire", ())))
    assert cb.slots[0].done and not cb.slots[1].done

    # one slot free: a synchronous wave
    cb.slots[0] = None
    cb._decode_step()
    (wave,) = rec.take()
    assert shape(wave) == ("serve.wave", (
        ("wave.inputs", ()), CALL, ("wave.sample", ()),
        ("wave.retire", ())))
    assert wave[1] == {"n_active": 1, "kv_live": 10 / 32}
    assert wave[2][1][1] == {"program": "stitched_decode_wave"}
    cb.slots = [None] * cb.n_slots


def test_lookup_and_launch_under_every_call(batcher, monkeypatch):
    cfg, cb = batcher
    rec = Recorder()
    monkeypatch.setattr(spans, "span", rec)
    for n in (5, 7, 11):
        cb.submit(_prompt(cfg, n), max_new=3)
    cb.run()
    calls = [n for n in walk(rec.take()) if n[0] == "stitch.call"]
    assert len(calls) >= 3 + 2
    for call in calls:
        kids = [k[0] for k in call[2]]
        assert kids[0] == "stitch.lookup" and kids[-1] == "stitch.launch"


def test_queue_wait_is_timed_at_admission(batcher):
    cfg, cb = batcher
    before = len(cb.stats.queue_wait)
    for n in (5, 7, 11):     # three requests, two slots: one waits
        cb.submit(_prompt(cfg, n), max_new=3)
    reqs = list(cb.queue)
    cb.run()
    waits = cb.stats.queue_wait[before:]
    ttft = cb.stats.ttft_s[-3:]
    assert len(waits) == 3
    for req, first in zip(reqs, ttft):
        assert req.t_submit <= req.t_admit <= req.t_submit + first
        assert (req.t_admit, req.t_admit - req.t_submit) in waits
    # the third waited for a wave: its queue time is the longest
    assert waits[2][1] > max(waits[0][1], waits[1][1])


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def test_plan_phases_sum_within_plan_time(tmp_path):
    args = (jnp.ones((16, 128)), jnp.ones((128,)), jnp.zeros((128,)))
    cold = StitchedFunction(_layer_norm, plan_cache=str(tmp_path))
    hit = StitchedFunction(_layer_norm, plan_cache=str(tmp_path))
    for sf, was_hit in ((cold, False), (hit, True)):
        rep = sf.report(*args)
        assert rep.plan_cache_hit is was_hit
        assert min(rep.trace_s, rep.search_s, rep.emit_s) > 0
        assert rep.trace_s + rep.search_s + rep.emit_s <= rep.plan_time_s


def _chain(x, r, g):
    h = x + r
    ms = jnp.mean(h.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    y = (h * jax.lax.rsqrt(ms + 1e-6) * g).astype(x.dtype)
    return jax.nn.gelu(y, approximate=True), h


def test_programs_and_kernels_carry_their_names():
    x, g = jnp.ones((16, 128)), jnp.ones((128,))
    c = stitched_jit(_chain).compiled(x, x, g)
    assert [e.name for e in c.emitted] == ["stitch_onepass_0"]
    lowered = c._jitted.lower(x, x, g)
    assert lowered.as_text().startswith("module @jit_stitched__chain")
    text = lowered.as_text(debug_info=True)
    assert re.search(r"jit\(stitched__chain\)/g0/stitch_onepass_0/", text)
    hand = jax.jit(lambda x, g: ops.rmsnorm(x, g)).lower(x, g)
    assert "/rmsnorm/pallas_call" in hand.as_text(debug_info=True)
