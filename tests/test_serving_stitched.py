"""ISSUE-6 tests: serving the compiler.

Covers: the bucket ladder (pow2 default, ``REPRO_SERVE_BUCKETS``
override, cap clamping, pad_tokens), stitched-vs-XLA decode equivalence
through the continuous batcher, bucket-boundary prompt lengths, EOS
mid-wave + mid-flight refill under the stitched path, the
compile-once-per-bucket guarantee (a 7-length prompt mix compiles one
prefill per bucket and exactly one decode wave), selective cache-leaf
donation (params and aliased outputs are never donated), the cold-miss
policy (a plan-cache miss serves the analytic plan without blocking on
measurement), and background hot-swap atomicity (in-flight calls keep a
fully valid dispatch while ``rerace`` races and swaps the measured
winner, which also persists to the plan cache).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotune as autotune_mod
from repro.core.plan_cache import PlanCache, entry_partition_source
from repro.core.stitch import StitchedFunction
from repro.launch.serve import generate
from repro.models import build_model
from repro.serving import BackgroundTuner, Buckets, ContinuousBatcher, \
    pad_tokens
from repro.serving.buckets import ENV_BUCKETS

rng = np.random.default_rng(17)


def _setup(arch="llama3.2-3b"):
    cfg = get_config(arch).reduced()
    mdl = build_model(cfg, fusion_mode="xla")
    params = mdl.init(jax.random.PRNGKey(0))
    return cfg, mdl, params


def _refs(mdl, params, prompts, gen):
    """Single-request XLA references (the ground truth every serving
    configuration must reproduce exactly -- greedy decode is bitwise)."""
    return [generate(mdl, params, p[None, :], gen,
                     stitched=False)[0, len(p):].tolist() for p in prompts]


# -- bucket ladder -------------------------------------------------------------
def test_buckets_pow2_default(monkeypatch):
    monkeypatch.delenv(ENV_BUCKETS, raising=False)
    bk = Buckets.from_env()
    assert bk.edges == ()
    # tiny prompts share the min_bucket floor
    assert [bk.bucket(n) for n in (1, 5, 8)] == [8, 8, 8]
    assert [bk.bucket(n) for n in (9, 16, 17, 100)] == [16, 16, 32, 128]
    # cap clamps a bucket to the slot's allocated cache length
    assert bk.pad_len(9, cap=12) == 12
    assert bk.pad_len(9, cap=64) == 16


def test_buckets_env_override(monkeypatch):
    monkeypatch.setenv(ENV_BUCKETS, "48,16,128")
    bk = Buckets.from_env()
    assert bk.edges == (16, 48, 128)   # sorted, deduped
    assert bk.bucket(10) == 16
    assert bk.bucket(16) == 16
    assert bk.bucket(17) == 48
    assert bk.bucket(128) == 128
    # beyond the ladder: pow2 fallback, floored at the last edge
    assert bk.bucket(129) == 256
    monkeypatch.setenv(ENV_BUCKETS, "0,8")
    with pytest.raises(ValueError):
        Buckets.from_env()


def test_pad_tokens():
    t = np.arange(5, dtype=np.int32)
    p = pad_tokens(t, 8, pad_id=7)
    assert p.tolist() == [0, 1, 2, 3, 4, 7, 7, 7]
    b = pad_tokens(np.stack([t, t]), 8)
    assert b.shape == (2, 8) and b[:, 5:].sum() == 0
    assert pad_tokens(t, 5) is t          # exact fit: no copy
    with pytest.raises(ValueError):
        pad_tokens(t, 4)


# -- stitched batcher correctness ----------------------------------------------
def test_stitched_batcher_matches_xla_generate():
    cfg, mdl, params = _setup()
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 5, 13, 6, 11)]
    gen = 5
    refs = _refs(mdl, params, prompts, gen)

    # 5 requests / 2 slots: mid-flight refill prefills into the live
    # stacked cache while other slots keep decoding.
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=64,
                               stitched=True)
    rids = [server.submit(p, max_new=gen) for p in prompts]
    results = server.run()
    for rid, ref in zip(rids, refs):
        assert results[rid] == ref, f"request {rid}: {results[rid]} != {ref}"
    assert server.stats.plan_cache_hits + server.stats.plan_cache_misses \
        == server.compile_counts()["prefill"] + \
        server.compile_counts()["decode"]


def test_bucket_boundary_lengths():
    """Prompt lengths straddling a bucket edge (edge-1, edge, edge+1)
    must all decode exactly: the padded tail is causally invisible."""
    cfg, mdl, params = _setup()
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 8, 9)]   # default ladder edge at 8
    gen = 4
    refs = _refs(mdl, params, prompts, gen)
    server = ContinuousBatcher(mdl, params, n_slots=3, max_len=48,
                               stitched=True)
    rids = [server.submit(p, max_new=gen) for p in prompts]
    results = server.run()
    for rid, ref in zip(rids, refs):
        assert results[rid] == ref
    # 7 and 8 share the 8-bucket; 9 pads to 16: exactly two prefills
    assert server.compile_counts() == {"prefill": 2, "decode": 1,
                                       "slot_write": 1}


def test_eos_mid_wave_and_refill():
    """A request hitting EOS mid-wave frees its slot for the queue; the
    survivors' streams are unperturbed by the refill prefill."""
    cfg, mdl, params = _setup()
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 5, 13, 7)]
    gen = 8
    refs = _refs(mdl, params, prompts, gen)
    # pick an EOS id that one reference emits mid-stream so the cut is
    # exercised, whatever the reduced model happens to sample.
    eos = refs[0][gen // 2]

    def cut(seq):
        return seq[: seq.index(eos) + 1] if eos in seq else seq

    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=64,
                               stitched=True, eos_id=eos)
    rids = [server.submit(p, max_new=gen) for p in prompts]
    results = server.run()
    assert set(results) == set(rids)
    for rid, ref in zip(rids, refs):
        assert results[rid] == cut(ref)
    assert any(len(results[rid]) < gen for rid in rids)  # EOS actually cut


def test_prompt_mix_compiles_once_per_bucket():
    """Satellite 2: a 7-length Zipf-ish prompt mix collapses onto its
    buckets -- one prefill compile per bucket, one decode compile total,
    and repeat shapes are hits, not replans."""
    cfg, mdl, params = _setup()
    lengths = (3, 5, 6, 7, 8, 9, 12)   # buckets: 8,8,8,8,8,16,16
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    server = ContinuousBatcher(mdl, params, n_slots=3, max_len=48,
                               stitched=True)
    for p in prompts:
        server.submit(p, max_new=3)
    server.run()
    assert server.compile_counts() == {"prefill": 2, "decode": 1,
                                       "slot_write": 1}
    assert server.stats.replans == 3          # 2 prefill shapes + 1 decode
    assert server.stats.shape_hits > 0
    assert 0.0 < server.stats.hit_rate < 1.0
    assert server.stats.tok_per_s_steady >= 0.0
    # the same mix resubmitted is all hits: zero new replans
    before = server.stats.replans
    for p in prompts:
        server.submit(p, max_new=3)
    server.run()
    assert server.stats.replans == before
    assert server.compile_counts() == {"prefill": 2, "decode": 1,
                                       "slot_write": 1}


def test_ssm_prompts_stay_exact():
    """Right-padding folds into a recurrent state: ssm/hybrid prefill
    keeps exact prompt lengths (and still serves correctly)."""
    cfg, mdl, params = _setup("mamba2-370m")
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=40)
    assert server._pad_prompts is False
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 11)]
    refs = _refs(mdl, params, prompts, 4)
    rids = [server.submit(p, max_new=4) for p in prompts]
    results = server.run()
    for rid, ref in zip(rids, refs):
        assert results[rid] == ref
    cfg2, mdl2, _ = _setup()
    assert ContinuousBatcher(mdl2, mdl2.init(jax.random.PRNGKey(0)),
                             max_len=32)._pad_prompts is True


# -- selective donation --------------------------------------------------------
def test_donate_argnums_cache_only():
    """Explicit donate_argnums donates exactly those flat positions --
    and silently drops any that alias an output (donating an aliased
    buffer would corrupt the result)."""
    def f(w, kv, tok):
        nkv = kv.at[0].set(tok)
        return (nkv * w).sum(), nkv

    w = jnp.ones((4, 8))
    kv = jnp.zeros((4, 8))
    tok = jnp.ones((8,))
    ref = jax.tree_util.tree_map(np.asarray, f(w, kv, tok))

    sf = StitchedFunction(f, donate_argnums=(1,))
    compiled = sf.compiled(w, kv, tok)
    assert compiled.donate_argnums == (1,)     # kv only, never the params
    out = sf(w, kv, tok)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-6)

    def g(w, kv):
        return kv, (kv * w).sum()              # kv aliases an output

    sf2 = StitchedFunction(g, donate_argnums=(1,))
    assert sf2.compiled(w, kv).donate_argnums == ()


# -- background cold-miss racing ----------------------------------------------
def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def _deep(x, g, b):
    for _ in range(8):
        x = _ln(x, g, b)
        x = jax.nn.gelu(x, approximate=True) + x
    return x


def _deep_args(R=16, C=256):
    return (rng.standard_normal((R, C)).astype(np.float32),
            (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32),
            rng.standard_normal(C).astype(np.float32))


def _gated_tune_partitions(gate: threading.Event, started: threading.Event):
    """The real partition race, held at the starting line until the test
    opens the gate -- makes cold-path/race interleaving deterministic."""
    real = autotune_mod.tune_partitions

    def wrapped(*a, **k):
        started.set()
        assert gate.wait(timeout=120.0), "test never opened the gate"
        return real(*a, **k)
    return wrapped


def test_cold_miss_serves_analytic_without_blocking(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    gate, started = threading.Event(), threading.Event()
    monkeypatch.setattr(autotune_mod, "tune_partitions",
                        _gated_tune_partitions(gate, started))
    args = _deep_args()
    ref = np.asarray(_deep(*(jnp.asarray(a) for a in args)))

    with BackgroundTuner() as tuner:
        sf = StitchedFunction(_deep, background=tuner,
                              plan_cache=str(tmp_path))
        out = np.asarray(sf(*args))          # returns while race is gated
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        rep = sf.reports()[0]
        assert rep.partition_source == "analytic"
        assert rep.partition_candidates >= 2
        assert tuner.stats.submitted == 1
        assert tuner.stats.completed == 0    # the race has not finished
        # cold store is model-sourced: a later process still races it
        entry = PlanCache(str(tmp_path)).load(rep.signature)
        assert entry_partition_source(entry) == "model"

        gate.set()
        assert tuner.drain(timeout=180.0)
        assert tuner.stats.swaps == 1 and tuner.stats.measured == 1
    rep2 = sf.reports()[0]
    assert rep2.partition_source == "measured"
    np.testing.assert_allclose(np.asarray(sf(*args)), ref,
                               rtol=2e-4, atol=2e-4)
    # the measured winner persisted: later processes replay, no re-race
    entry = PlanCache(str(tmp_path)).load(rep2.signature)
    assert entry_partition_source(entry) == "measured"


def test_hot_swap_atomic_under_traffic(monkeypatch, tmp_path):
    """In-flight calls keep executing a fully valid dispatch while the
    background race runs, through the swap, and after it."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    gate, started = threading.Event(), threading.Event()
    monkeypatch.setattr(autotune_mod, "tune_partitions",
                        _gated_tune_partitions(gate, started))
    args = _deep_args()
    ref = np.asarray(_deep(*(jnp.asarray(a) for a in args)))

    def check():
        np.testing.assert_allclose(np.asarray(sf(*args)), ref,
                                   rtol=2e-4, atol=2e-4)

    with BackgroundTuner() as tuner:
        sf = StitchedFunction(_deep, background=tuner,
                              plan_cache=str(tmp_path))
        check()                               # cold call, race now queued
        old = next(iter(sf._cache.values()))
        assert started.wait(timeout=120.0)
        for _ in range(3):
            check()                           # racing: old instance serves
        gate.set()
        # hammer the dispatch through the swap window: every call must
        # see either the old or the new instance, never a half-built one
        while tuner.stats.completed == 0:
            check()
        check()
        assert tuner.drain(timeout=60.0)
    new = next(iter(sf._cache.values()))
    assert new is not old                     # the swap really happened
    assert new.report.partition_source == "measured"
    check()


def test_background_tuner_survives_job_failure():
    with BackgroundTuner() as tuner:
        tuner.submit(lambda: 1 / 0)
        tuner.submit(lambda: "measured")
        tuner.submit(lambda: None)
        assert tuner.drain(timeout=30.0)
    assert tuner.stats.submitted == 3
    assert tuner.stats.completed == 3
    assert tuner.stats.failed == 1
    assert tuner.stats.swaps == 1
    assert tuner.stats.measured == 1
    assert tuner.stats.sources == [None, "measured", None]


def test_batcher_with_background_tuner_still_exact(monkeypatch, tmp_path):
    """End-to-end: the serving scheduler wired to a BackgroundTuner on a
    cold plan cache still reproduces the XLA reference exactly, and
    drains cleanly."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    cfg, mdl, params = _setup()
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9)]
    refs = _refs(mdl, params, prompts, 4)
    with BackgroundTuner() as tuner:
        server = ContinuousBatcher(mdl, params, n_slots=2, max_len=48,
                                   stitched=True,
                                   plan_cache=str(tmp_path),
                                   background=tuner)
        rids = [server.submit(p, max_new=4) for p in prompts]
        results = server.run()
        for rid, ref in zip(rids, refs):
            assert results[rid] == ref
        assert tuner.drain(timeout=300.0)
        assert tuner.stats.failed == 0
    # post-swap waves still exact
    rids2 = [server.submit(p, max_new=4) for p in prompts]
    results2 = server.run()
    for rid, ref in zip(rids2, refs):
        assert results2[rid] == ref
