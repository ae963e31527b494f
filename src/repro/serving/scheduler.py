"""Continuous-batching serving scheduler -- a client of the stitching
compiler.

vLLM-style slot management adapted to the JAX step model: a fixed pool
of ``n_slots`` decode slots advances in lock-step (one jitted vmap'd
decode per wave), each slot carrying its own KV/SSM cache and position;
finished slots are refilled from the queue mid-flight via a single-slot
prefill written into the stacked cache (no global re-batch, no pause of
in-flight requests).  The prefill fills an empty one-slot cache built
once, and one jitted program writes the filled slot into the stacked
cache at a traced slot index -- one compile for every slot and prompt
length, with the stacked cache donated as the wave donates it, so XLA
writes the one slot in place instead of copying every stacked leaf.

Serving the compiler (paper §7, tune-once-run-many):

* prefill and the decode wave dispatch through ``stitched_jit`` (unless
  the model was built with ``fusion_mode="xla"``), so every wave runs
  the beam-searched, plan-cached stitched schedule as ONE dispatch;
* prompt lengths are canonicalized onto a small bucket ladder
  (``serving.buckets``), so a Zipfian mix of live shapes collapses onto
  a handful of plan-cache signatures -- after warmup ~every request
  hits an already-compiled plan (padding is masked; see buckets.py);
* the stacked KV/SSM cache is *donated* across decode waves
  (``donate_argnums`` names the cache leaves only, never the params),
  so XLA updates it in place instead of round-tripping through HBM;
* with a ``BackgroundTuner``, a cold plan-cache miss serves the
  analytic plan immediately while the top-k partition race runs in the
  background and hot-swaps the measured winner into the live dispatch.

The chip does not wait on the host between waves while the next wave is
certain: ``_decode_step`` dispatches wave n+1 on wave n's logits before
it reads wave n back (see ``ContinuousBatcher``).

Simplifications vs a full vLLM (documented): greedy decoding; idle slots
still burn a decode lane (masked out functionally); prefills are
one-slot-at-a-time (chunked-prefill interleaving is future work);
recurrent-cache families (ssm/hybrid) keep exact prompt lengths, since
right-padding is not inert through a recurrence.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.stitch import StitchedFunction, stitched_jit
from repro.models.model import Model
from repro.runtime import spans
from repro.runtime.canary import CanaryController

from .buckets import Buckets, pad_tokens


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new: int
    out: list[int] = field(default_factory=list)
    pos: int = 0                  # next cache position
    done: bool = False
    t_submit: float = 0.0         # perf_counter at submit (TTFT anchor)
    t_admit: float = 0.0          # perf_counter at the start of its prefill


def _pct(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


@dataclass
class ServeStats:
    prefills: int = 0
    decode_waves: int = 0
    waves_ahead: int = 0       # ...dispatched before the last was read back
    tokens_out: int = 0
    wall_s: float = 0.0
    # -- shape canonicalization / replans ------------------------------------
    shape_hits: int = 0        # dispatch calls on an already-compiled shape
    shape_misses: int = 0      # ...that traced+planned fresh (replans)
    compile_s: float = 0.0     # wall spent inside cold (first-shape) calls
    # -- persistent plan cache (from StitchReport, stitched path only) -------
    plan_cache_hits: int = 0   # compiled signatures loaded from disk
    plan_cache_misses: int = 0  # ...planned from scratch
    # -- guard layer (fallback ladder / verification / background tuner) -----
    fallbacks: int = 0         # degradations recorded across live plans
    quarantined: int = 0       # plans pinned to the XLA baseline rung
    verified: int = 0          # dispatches shadow-verified against XLA
    verify_failures: int = 0   # ...that mismatched
    tuner_failed: int = 0      # background tuning jobs that failed
    tuner_last_error: str = ""  # most recent tuner failure, verbatim
    # -- canary loop (live-traffic shadow sampling + plan health) -------------
    canaried: int = 0          # dispatches the canary shadow-verified
    canary_mismatches: int = 0  # ...that diverged (reference served)
    canary_skipped_budget: int = 0  # sampled verifies the budget refused
    canary_quarantines: int = 0  # signatures tripped to quarantined
    canary_probations: int = 0   # quarantined -> probation transitions
    canary_readmits: int = 0     # probation -> healthy re-admissions
    canary_baseline_serves: int = 0  # quarantined-state baseline serves
    canary_overhead_pct: float = 0.0  # budgeted verify cost / serve cost
    # -- latency samples ------------------------------------------------------
    ttft_s: list = field(default_factory=list)   # submit -> first token
    wave_s: list = field(default_factory=list)   # per decode wave
    #: (t_admit, t_admit - t_submit) per prefill: time in the queue
    queue_wait: list = field(default_factory=list)
    steady_wall_s: float = 0.0  # wall in warm (already-compiled) calls
    steady_tokens: int = 0      # tokens produced by warm calls

    @property
    def tok_per_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0

    @property
    def tok_per_s_steady(self) -> float:
        """Throughput excluding compile time: tokens from warm calls
        over warm-call wall (the fleet-amortized rate)."""
        return (self.steady_tokens / self.steady_wall_s
                if self.steady_wall_s else 0.0)

    @property
    def hit_rate(self) -> float:
        n = self.shape_hits + self.shape_misses
        return self.shape_hits / n if n else 0.0

    @property
    def replans(self) -> int:
        return self.shape_misses

    @property
    def p50_ttft_s(self) -> float:
        return _pct(self.ttft_s, 50)

    @property
    def p99_ttft_s(self) -> float:
        return _pct(self.ttft_s, 99)

    @property
    def p50_wave_s(self) -> float:
        return _pct(self.wave_s, 50)

    @property
    def p99_wave_s(self) -> float:
        return _pct(self.wave_s, 99)

    def summary(self) -> str:
        ahead = (self.waves_ahead / self.decode_waves
                 if self.decode_waves else 0.0)
        out = (f"{self.prefills} prefills, {self.decode_waves} decode "
               f"waves ({ahead:.1%} ahead), {self.tokens_out} tokens | "
               f"shape hit rate "
               f"{self.hit_rate:.1%} ({self.replans} replans) | "
               f"plan-cache {self.plan_cache_hits}h/"
               f"{self.plan_cache_misses}m | ttft p50/p99 "
               f"{self.p50_ttft_s * 1e3:.1f}/{self.p99_ttft_s * 1e3:.1f}ms"
               f" | wave p50/p99 {self.p50_wave_s * 1e3:.1f}/"
               f"{self.p99_wave_s * 1e3:.1f}ms | "
               f"{self.tok_per_s:.1f} tok/s "
               f"({self.tok_per_s_steady:.1f} steady)")
        if self.canaried or self.canary_quarantines \
                or self.canary_baseline_serves:
            out += (f" | canary {self.canaried}v/"
                    f"{self.canary_mismatches}x "
                    f"q{self.canary_quarantines}/"
                    f"p{self.canary_probations}/"
                    f"r{self.canary_readmits} "
                    f"{self.canary_overhead_pct:.2f}%")
        return out


class ContinuousBatcher:
    """A pool of decode slots served in lock-step waves.

    **Running ahead.**  ``_decode_step`` runs one wave and returns with
    one more token on every active request.  When the next wave is
    certain -- every slot holds a request (so no arrival could be
    admitted before it anyway), none of them stops at this wave by
    length, and there is no ``eos_id`` -- it dispatches the next wave
    *before* reading this one back, on inputs made on the device from
    this wave's logits (``argmax``) and positions (+1).  The next call
    then finds its wave in flight.  The program, its shapes and the
    tokens served are the same either way; only the host's steps overlap
    the device's.

    A wave dispatched ahead is committed: its tokens belong to the
    requests it served, and only reading them is deferred.  So whatever
    changes the state from outside ``_decode_step`` -- assigning
    ``cache`` or ``slots``, or a prefill -- first reads the pending wave
    back into its requests (as the next call would) and releases its
    logits.  So no wave outlives the cache it ran on, and no new state
    meets a wave meant for the old one.  Between calls a caller may clear
    a slot or fill a cleared one through ``_prefill_slot``.
    """

    def __init__(self, mdl: Model, params, *, n_slots: int = 4,
                 max_len: int = 256, eos_id: int | None = None,
                 stitched: bool | None = None,
                 buckets: Buckets | None = None,
                 plan_cache: str | None = None,
                 autotune: bool = False,
                 background=None,
                 donate: bool | None = None,
                 pad_id: int = 0,
                 canary=None):
        self.mdl = mdl
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.queue: deque[Request] = deque()
        #: ``(logits, positions)`` of the wave dispatched and not read back
        self._wave: tuple | None = None
        self.slots: list[Request | None] = [None] * n_slots
        self._ids = itertools.count()
        self.stats = ServeStats()
        self.stitched = (mdl.fusion_mode != "xla" if stitched is None
                         else stitched)
        self.buckets = buckets if buckets is not None else Buckets.from_env()
        # right-padding is masked for attention caches but folds into a
        # recurrent state -- exact lengths for ssm/hybrid prefill.
        self._pad_prompts = mdl.cfg.family not in ("ssm", "hybrid")
        # XLA ignores donation on CPU (and warns); auto-enable elsewhere.
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._seen_shapes: set[tuple] = set()
        self._background = background  # tuner stats surface on ServeStats
        # one canary controller shared by prefill + decode: the overhead
        # budget is per serving process, not per dispatch callable.
        if canary is None and self.stitched:
            canary = CanaryController.from_env(plan_cache)
        self._canary = canary if self.stitched else None

        #: the empty one-slot cache every prefill fills: arrays are
        #: immutable and the prefill donates none of its arguments
        self._empty_slot = mdl.init_cache(1, max_len)
        self.cache = jax.tree_util.tree_map(
            lambda x: jnp.zeros((n_slots,) + x.shape, x.dtype),
            self._empty_slot)
        #: leaves a prefill writes (the cache-span stat)
        self._cache_leaves = len(jax.tree_util.tree_leaves(self._empty_slot))

        # the function names name the stitched programs
        # (``stitched_prefill``, ``stitched_decode_wave``)
        def prefill(p, t, c):
            return mdl.prefill(p, tokens=t, cache=c)

        # params are an explicit argument (NOT a closure): a closed-over
        # pytree gets baked into the trace as embedded constants, which
        # bloats every compile, defeats donation analysis, and silently
        # serves stale weights after a param swap.
        def decode_one(p, cache_slot, tok, pos):
            logits, nc = mdl.decode_step(p, cache_slot, tok, pos,
                                         kv_len=pos + 1)
            return logits[:, -1, : mdl.cfg.vocab_size], nc

        def decode_wave(p, cache, toks, poss):
            return jax.vmap(decode_one, in_axes=(None, 0, 0, 0))(
                p, cache, toks, poss)

        if self.stitched:
            self._prefill = stitched_jit(
                prefill, plan_cache=plan_cache, autotune=autotune,
                background=background, canary=self._canary)
            # donate exactly the cache leaves of the wave's flat
            # signature (params..., cache..., toks, poss): the stacked
            # KV/SSM cache updates in place across waves.
            n_p = len(jax.tree_util.tree_leaves(params))
            n_c = len(jax.tree_util.tree_leaves(self.cache))
            self._decode_wave = stitched_jit(
                decode_wave, plan_cache=plan_cache, autotune=autotune,
                background=background, canary=self._canary,
                donate_argnums=(tuple(range(n_p, n_p + n_c))
                                if donate else None))
        else:
            self._prefill = jax.jit(prefill)
            self._decode_wave = jax.jit(
                decode_wave, donate_argnums=(1,) if donate else ())

        # a wave's tokens and the next wave's positions, in one dispatch
        # on the device: the inputs of a wave dispatched ahead
        def advance(logits, poss):
            toks = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            return toks[:, None, None], poss + 1

        self._advance = jax.jit(advance)

        # a prefilled slot enters the stacked cache at a traced index:
        # one compile serves every slot and prompt length, and with the
        # cache donated XLA writes the slot in place
        def write_slot(cache, filled, i):
            return jax.tree_util.tree_map(
                lambda st, c: lax.dynamic_update_index_in_dim(st, c, i, 0),
                cache, filled)

        self._write_slot = jax.jit(
            write_slot, donate_argnums=(0,) if donate else ())

    @property
    def cache(self):
        """The stacked KV/SSM cache of every slot."""
        return self._cache

    @cache.setter
    def cache(self, value) -> None:
        self._settle()
        self._cache = value

    @property
    def slots(self) -> list[Request | None]:
        """The request each slot serves, or ``None``."""
        return self._slots

    @slots.setter
    def slots(self, value: list[Request | None]) -> None:
        self._settle()
        self._slots = value

    # -- client API -----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int = 16) -> int:
        assert len(prompt) + max_new <= self.max_len, "request exceeds slot"
        req = Request(next(self._ids), np.asarray(prompt, np.int32), max_new,
                      t_submit=time.perf_counter())
        self.queue.append(req)
        return req.rid

    def run(self) -> dict[int, list[int]]:
        """Drive until queue + slots drain.  Returns rid -> generated ids."""
        t0 = time.perf_counter()
        results: dict[int, list[int]] = {}
        self._fill_slots()
        while any(s is not None for s in self.slots):
            self._decode_step()
            for i, req in enumerate(self.slots):
                if req is not None and req.done:
                    results[req.rid] = req.out
                    self.slots[i] = None
            self._fill_slots()
        self.stats.wall_s += time.perf_counter() - t0
        self._sync_plan_reports()
        return results

    def compile_counts(self) -> dict[str, int]:
        """Distinct traced shape signatures per dispatch callable
        (tests assert a 7-length prompt mix compiles once per bucket)."""
        def count(fn) -> int:
            if isinstance(fn, StitchedFunction):
                return fn.n_compiled
            try:
                return fn._cache_size()
            except Exception:  # noqa: BLE001 -- older jax without the API
                return -1
        return {"prefill": count(self._prefill),
                "decode": count(self._decode_wave),
                "slot_write": count(self._write_slot)}

    # -- internals ---------------------------------------------------------------
    def _note_call(self, shape_key: tuple, dt: float, tokens: int) -> None:
        if shape_key in self._seen_shapes:
            self.stats.shape_hits += 1
            self.stats.steady_wall_s += dt
            self.stats.steady_tokens += tokens
        else:
            self._seen_shapes.add(shape_key)
            self.stats.shape_misses += 1
            self.stats.compile_s += dt

    def _sync_plan_reports(self) -> None:
        """Surface persistent plan-cache hit/miss and guard-layer
        degradations (fallback rungs, quarantines, shadow-verification
        counters, background-tuner failures) from StitchReports: a
        contained failure never raises on the serving path, so the
        stats are where an operator learns it happened."""
        if not self.stitched:
            return
        hits = misses = 0
        fallbacks = quarantined = verified = verify_failures = 0
        for fn in (self._prefill, self._decode_wave):
            for rep in fn.reports():
                hits += rep.plan_cache_hit
                misses += not rep.plan_cache_hit
                fallbacks += len(rep.fallbacks)
                quarantined += rep.quarantined
                verified += rep.verified
                verify_failures += rep.verify_failures
        self.stats.plan_cache_hits = hits
        self.stats.plan_cache_misses = misses
        self.stats.fallbacks = fallbacks
        self.stats.quarantined = quarantined
        self.stats.verified = verified
        self.stats.verify_failures = verify_failures
        tstats = getattr(self._background, "stats", None)
        if tstats is not None:
            self.stats.tuner_failed = getattr(tstats, "failed", 0)
            self.stats.tuner_last_error = getattr(tstats, "last_error", "")
        if self._canary is not None:
            cs = self._canary.stats
            self.stats.canaried = cs.verified
            self.stats.canary_mismatches = cs.mismatches
            self.stats.canary_skipped_budget = cs.skipped_budget
            self.stats.canary_quarantines = cs.quarantines
            self.stats.canary_probations = cs.probations
            self.stats.canary_readmits = cs.readmits
            self.stats.canary_baseline_serves = cs.baseline_serves
            self.stats.canary_overhead_pct = self._canary.overhead_pct

    def _fill_slots(self) -> None:
        for i in range(self.n_slots):
            if self.slots[i] is None and self.queue:
                req = self.queue.popleft()
                self._prefill_slot(i, req)
                self.slots[i] = req

    def _prefill_slot(self, i: int, req: Request) -> None:
        self._settle()
        t0 = req.t_admit = time.perf_counter()
        self.stats.queue_wait.append((t0, t0 - req.t_submit))
        true_len = len(req.prompt)
        with spans.span("serve.prefill", rid=req.rid, slot=i,
                        plen=true_len):
            if self._pad_prompts:
                plen = self.buckets.pad_len(true_len, cap=self.max_len)
                toks = pad_tokens(req.prompt, plen, pad_id=self.pad_id)
            else:
                toks = req.prompt
            logits, filled = self._prefill(self.params, toks[None, :],
                                           self._empty_slot)
            with spans.span("prefill.cache_write",
                            leaves=self._cache_leaves):
                self._cache = self._write_slot(self._cache, filled,
                                               np.int32(i))
            with spans.span("prefill.sample"):
                # the *true* last prompt position: the causal mask makes
                # the padded tail invisible to it.
                first = int(jnp.argmax(
                    logits[0, true_len - 1, : self.mdl.cfg.vocab_size]))
            dt = time.perf_counter() - t0
            self._note_call(("prefill", int(toks.shape[-1])), dt, tokens=1)
            req.out.append(first)
            req.pos = true_len
            self.stats.prefills += 1
            self.stats.tokens_out += 1
            self.stats.ttft_s.append(time.perf_counter() - req.t_submit)
            self._check_done(req)

    def _decode_step(self, ahead: bool = True) -> None:
        active = [i for i, req in enumerate(self.slots)
                  if req is not None and not req.done]
        if not active:
            self._wave = None   # the caller cleared every slot it served
            return
        with spans.span("serve.wave", n_active=len(active)):
            t0 = time.perf_counter()
            if self._wave is None:
                with spans.span("wave.inputs"):
                    toks = np.zeros((self.n_slots, 1, 1), np.int32)
                    poss = np.zeros((self.n_slots,), np.int32)
                    for i in active:
                        toks[i, 0, 0] = self.slots[i].out[-1]
                        poss[i] = self.slots[i].pos
                    toks, poss = jnp.asarray(toks), jnp.asarray(poss)
                self._dispatch(toks, poss)
            toks, poss = self._advance(*self._wave)
            self._wave = None
            if ahead and self._next_wave_certain(active):
                with spans.span("wave.ahead"):
                    self._dispatch(toks, poss)
                self.stats.waves_ahead += 1
            with spans.span("wave.sample"):
                nxt = np.asarray(toks)[:, 0, 0]
            dt = time.perf_counter() - t0
            self.stats.wave_s.append(dt)
            self._note_call(("decode",), dt, tokens=len(active))
            with spans.span("wave.retire"):
                for i in active:
                    req = self.slots[i]
                    req.out.append(int(nxt[i]))
                    req.pos += 1
                    self.stats.tokens_out += 1
                    self._check_done(req)

    def _dispatch(self, toks, poss) -> None:
        """Launch one decode wave; it stays in flight until read back."""
        logits, self._cache = self._decode_wave(
            self.params, self._cache, toks, poss)
        self._wave = (logits, poss)
        self.stats.decode_waves += 1

    def _next_wave_certain(self, active: list[int]) -> bool:
        """Whether the wave after the one in flight runs whatever its
        tokens: every slot is held and no request stops by length once
        the wave in flight adds its token."""
        return (self.eos_id is None and len(active) == self.n_slots
                and not any(self._stops_by_length(self.slots[i], 1)
                            for i in active))

    def _settle(self) -> None:
        """Read a wave dispatched ahead back into its requests."""
        if self._wave is not None:
            self._decode_step(ahead=False)

    def _stops_by_length(self, req: Request, more: int = 0) -> bool:
        """Whether ``req`` is done by length after ``more`` tokens."""
        return (len(req.out) + more >= req.max_new
                or req.pos + more + 1 >= self.max_len)

    def _check_done(self, req: Request) -> None:
        if self._stops_by_length(req) or \
                (self.eos_id is not None and req.out[-1] == self.eos_id):
            req.done = True
