"""Continuous-batching scheduler: exactness vs single-request generation,
mid-flight slot refill, mixed prompt lengths, and waves dispatched ahead
of the last one's read-back."""
import gc
import weakref

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import generate
from repro.models import build_model
from repro.serving import ContinuousBatcher

rng = np.random.default_rng(9)


def _setup(arch="llama3.2-3b"):
    cfg = get_config(arch).reduced()
    mdl = build_model(cfg, fusion_mode="xla")
    params = mdl.init(jax.random.PRNGKey(0))
    return cfg, mdl, params


def test_batched_equals_single_request():
    cfg, mdl, params = _setup()
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 5, 13)]
    gen = 6

    server = ContinuousBatcher(mdl, params, n_slots=3, max_len=64)
    rids = [server.submit(p, max_new=gen) for p in prompts]
    results = server.run()

    for rid, prompt in zip(rids, prompts):
        ref = generate(mdl, params, prompt[None, :], gen)[0, len(prompt):]
        assert results[rid] == ref.tolist(), \
            f"request {rid}: {results[rid]} != {ref.tolist()}"


def test_slot_refill_more_requests_than_slots():
    cfg, mdl, params = _setup()
    prompts = [rng.integers(0, cfg.vocab_size, 4 + i).astype(np.int32)
               for i in range(5)]
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=48)
    rids = [server.submit(p, max_new=4) for p in prompts]
    results = server.run()
    assert set(results) == set(rids)
    assert all(len(v) == 4 for v in results.values())
    assert server.stats.prefills == 5
    assert server.stats.tokens_out == 20


def test_ssm_family_serves_too():
    cfg, mdl, params = _setup("mamba2-370m")
    prompts = [rng.integers(0, cfg.vocab_size, 7).astype(np.int32),
               rng.integers(0, cfg.vocab_size, 11).astype(np.int32)]
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=40)
    rids = [server.submit(p, max_new=5) for p in prompts]
    results = server.run()
    for rid, prompt in zip(rids, prompts):
        ref = generate(mdl, params, prompt[None, :], 5)[0, len(prompt):]
        assert results[rid] == ref.tolist()


def _generated(mdl, params, prompt, n):
    return generate(mdl, params, prompt[None, :], n)[0, len(prompt):].tolist()


@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-370m",
                                  "zamba2-1.2b"])
def test_waves_ahead_serve_the_same_tokens(arch):
    """Every slot held, refills between: waves run ahead and each request
    still gets exactly its single-request tokens."""
    cfg, mdl, params = _setup(arch)
    lens, gens = (6, 9, 5, 8, 7), (7, 4, 9, 5, 6)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=32)
    rids = [server.submit(p, max_new=g) for p, g in zip(prompts, gens)]
    results = server.run()
    assert server.stats.waves_ahead > 0
    assert server.stats.tokens_out == sum(gens)
    assert server._wave is None
    for rid, prompt, g in zip(rids, prompts, gens):
        assert results[rid] == _generated(mdl, params, prompt, g), rid


@pytest.mark.parametrize("why", ["free_slot", "eos"])
def test_no_wave_ahead_when_the_next_is_not_certain(why):
    cfg, mdl, params = _setup()
    kw = ({"n_slots": 3} if why == "free_slot"
          else {"n_slots": 2, "eos_id": cfg.vocab_size + 1})
    server = ContinuousBatcher(mdl, params, max_len=32, **kw)
    for n in (5, 7):
        server.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      max_new=6)
    results = server.run()
    assert all(len(v) == 6 for v in results.values())
    assert server.stats.decode_waves == 5
    assert server.stats.waves_ahead == 0


def _in_flight(cfg, mdl, params):
    """A batcher whose two slots are held, left with a wave in flight."""
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=32)
    reqs = []
    for n in (5, 7):
        server.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      max_new=8)
        reqs.append(server.queue[-1])
    server._fill_slots()
    server._decode_step()
    assert server.stats.waves_ahead == 1 and server._wave is not None
    return server, reqs


def test_assigning_cache_and_slots_leaves_no_wave_behind():
    """As the benchmark's ``reset_cache`` does: the old cache and the
    wave in flight are freed, and the next run serves correct tokens."""
    cfg, mdl, params = _setup("mamba2-370m")
    server, reqs = _in_flight(cfg, mdl, params)
    old_leaf = weakref.ref(jax.tree_util.tree_leaves(server.cache)[0])
    old_logits = weakref.ref(server._wave[0])
    server.cache = None
    gc.collect()
    assert old_leaf() is None and old_logits() is None
    assert server._wave is None
    assert [len(r.out) for r in reqs] == [3, 3]   # the wave was read back
    one = mdl.init_cache(1, server.max_len)
    server.cache = jax.tree_util.tree_map(
        lambda x: jax.numpy.zeros((server.n_slots,) + x.shape, x.dtype), one)
    server.queue.clear()
    server.slots = [None] * server.n_slots

    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 4, 9)]
    rids = [server.submit(p, max_new=5) for p in prompts]
    results = server.run()
    for rid, prompt in zip(rids, prompts):
        assert results[rid] == _generated(mdl, params, prompt, 5), rid


def test_prefill_over_a_slot_in_flight_keeps_the_others():
    """A slot cleared while a wave is in flight and refilled: the wave's
    token still reaches the request in the other slot."""
    cfg, mdl, params = _setup("mamba2-370m")
    server, (_, kept) = _in_flight(cfg, mdl, params)
    server.slots[0] = None
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    rid = server.submit(prompt, max_new=5)
    results = server.run()
    assert results[rid] == _generated(mdl, params, prompt, 5)
    assert results[kept.rid] == _generated(mdl, params, kept.prompt, 8)


def test_each_step_adds_one_token_and_no_wave_outlives_a_stop():
    """Driven step by step, as the benchmark's window drives it: every
    call adds one token to each active request, and a call after which a
    request stops leaves no wave in flight."""
    cfg, mdl, params = _setup("mamba2-370m")
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=32)
    for n, g in ((5, 4), (7, 6), (6, 5), (4, 3)):
        server.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      max_new=g)
    stops = 0
    while server.queue or any(server.slots):
        server._fill_slots()
        active = [r for r in server.slots if r is not None and not r.done]
        before = [len(r.out) for r in active]
        server._decode_step()
        assert [len(r.out) for r in active] == [b + 1 for b in before]
        if any(r.done for r in active):
            stops += 1
            assert server._wave is None
        for i, r in enumerate(server.slots):
            if r is not None and r.done:
                server.slots[i] = None
    assert stops >= 3 and server.stats.waves_ahead > 0


@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-370m",
                                  "zamba2-1.2b", "granite-4.0-h-small"])
def test_prefill_writes_its_slot_and_only_it(arch):
    """Attention, scanned SSM, hybrid and layer-pattern caches: the
    prefilled slot holds exactly what the prefill made, and every other
    slot keeps its state bit for bit."""
    cfg, mdl, params = _setup(arch)
    server = ContinuousBatcher(mdl, params, n_slots=3, max_len=32)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 256))
    server.cache = jax.tree_util.tree_map(
        lambda x: jax.random.normal(next(keys), x.shape, x.dtype) + 1.0,
        server.cache)
    before = jax.tree_util.tree_map(np.asarray, server.cache)
    made = []

    def prefill(*args):
        out = real(*args)
        made.append(out[1])
        return out

    real, server._prefill = server._prefill, prefill
    server.submit(rng.integers(0, cfg.vocab_size, 7).astype(np.int32),
                  max_new=4)
    server._prefill_slot(1, server.queue.popleft())
    (filled,) = made
    after = jax.tree_util.tree_leaves(server.cache)
    assert len(after) == len(jax.tree_util.tree_leaves(filled))
    for st, old, c in zip(after, jax.tree_util.tree_leaves(before),
                          jax.tree_util.tree_leaves(filled)):
        st = np.asarray(st)
        np.testing.assert_array_equal(st[1], np.asarray(c))
        np.testing.assert_array_equal(st[0], old[0])
        np.testing.assert_array_equal(st[2], old[2])


def test_slot_write_compiles_once_for_every_slot_and_length():
    """Every slot at prompt lengths of two buckets: one slot-write
    program serves every prefill."""
    cfg, mdl, params = _setup()
    server = ContinuousBatcher(mdl, params, n_slots=3, max_len=48)
    for n in (5, 12, 20):
        for i in range(server.n_slots):
            server.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                          max_new=2)
            server._prefill_slot(i, server.queue.popleft())
    assert server.stats.prefills == 9
    counts = server.compile_counts()
    assert counts["prefill"] == 3 and counts["slot_write"] == 1
