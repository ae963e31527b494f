"""Set up the served program and drive it through one window.

The window drives ``ContinuousBatcher`` through its own steps, as its
``run()`` loop does, so that requests can arrive during it: submit what
is due, refill free slots one prefill at a time, run one decode wave,
collect finished requests.  Every token gets a host timestamp: a first
token at the end of its prefill call, every later token at the end of
the wave that made it.  Both calls end in a host sync.  An open loop's
requests are timed from when they were due, not from when they were
submitted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from dataclasses import dataclass, field

import numpy as np

from .traffic import Req, Traffic

clock = time.perf_counter
#: seconds past the close an open loop waits for first tokens of the
#: requests due in the window
DRAIN_LIMIT_S = 60.0


def program_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file: its own
    preset with every size the file states."""
    from repro.configs import get_config

    base = get_config(config["program_arch"])
    names = {f.name for f in dataclasses.fields(base)}
    cfg = dataclasses.replace(
        base, **{k: v for k, v in config["model"].items() if k in names})
    if cfg.padded_vocab != config["model"]["vocab_rows"]:
        raise ValueError(f"the program pads the vocabulary to "
                         f"{cfg.padded_vocab} rows, the file says "
                         f"{config['model']['vocab_rows']}")
    return cfg


def make_params(ref, model: dict, seed: int):
    """Every weight, made on the device from ``seed`` in one jitted
    call, in float32 (the type it is served in)."""
    import jax

    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.block_until_ready(
        jax.jit(lambda k: ref.init_params(model, k))(key))


class StitchError(RuntimeError):
    """The stitched path stepped aside (fallback, quarantine, low rung)."""


def stitch_summary(cb) -> dict:
    """Plan counts of the batcher's stitched programs; refuses any sign
    that the stitched path stepped aside."""
    from repro.runtime.guard import RUNG_ANCHORED, RUNG_STITCHED

    reps = cb._prefill.reports() + cb._decode_wave.reports()
    if not reps:
        raise StitchError("no stitched program was compiled")
    for rep in reps:
        if rep.fallbacks:
            raise StitchError(f"fallback recorded: {rep.fallbacks}")
        if rep.quarantined:
            raise StitchError("a dispatch was quarantined")
        if rep.rung not in (RUNG_ANCHORED, RUNG_STITCHED):
            raise StitchError(f"served from rung {rep.rung!r}")
    return {"programs": len(reps),
            "plan_s": sum(r.plan_time_s for r in reps),
            "groups": sum(r.n_groups for r in reps),
            "anchored": sum(r.n_anchored for r in reps),
            "plan_cache_hits": sum(bool(r.plan_cache_hit) for r in reps),
            "rungs": sorted({r.rung for r in reps})}


@dataclass
class Window:
    t0: float = 0.0
    t_close: float = 0.0
    prefills: list = field(default_factory=list)   # (start, end, plen)
    waves: list = field(default_factory=list)      # (start, end, kv_lens)
    traced_waves: list = field(default_factory=list)  # indices in waves
    reqs: list = field(default_factory=list)       # Req, in arrival order
    finished: list = field(default_factory=list)   # Req, in finish order
    lag_s: list = field(default_factory=list)      # submit - due (open)
    unserved: int = 0       # due in the window, no first token after drain
    compiles: int = 0       # backend compiles inside the window
    queue_at_close: int = 0  # requests waiting for a slot at the close

    @property
    def seconds(self) -> float:
        return self.t_close - self.t0

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.reqs for t in r.times
                   if self.t0 <= t <= self.t_close)


class Spans:
    """Host spans into the profiler's trace, only while tracing."""

    def __init__(self):
        self.on = False

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class Server:
    """The served model, its batcher and the compiled programs."""

    def __init__(self, cell, fusion: str, plan_dir: str | None):
        from repro.models import build_model

        self.cell = cell
        self.mix = cell.mix
        self.ref = cell.reference()
        self.mdl = build_model(program_config(cell.config),
                               fusion_mode=fusion)
        self.plan_dir = plan_dir
        self.cb = None
        self.stitch: dict | None = None
        self.first_call_s = 0.0

    def load(self, seed: int):
        """Weights for ``seed`` and a batcher with an empty cache."""
        from repro.serving.scheduler import ContinuousBatcher

        if self.cb is not None:
            self.cb.params = None
        params = make_params(self.ref, self.cell.model, seed)
        if self.cb is None:
            self.cb = ContinuousBatcher(
                self.mdl, params, n_slots=self.mix["slots"],
                max_len=self.mix["max_len"], autotune=False,
                plan_cache=self.plan_dir)
        else:
            self.cb.params = params
            self.reset_cache()
        return params

    def reset_cache(self):
        import jax
        import jax.numpy as jnp

        cb = self.cb
        cb.cache = None
        gc.collect()
        one = cb.mdl.init_cache(1, cb.max_len)
        cb.cache = jax.tree_util.tree_map(
            lambda x: jnp.zeros((cb.n_slots,) + x.shape, x.dtype), one)
        cb.queue.clear()
        cb.slots = [None] * cb.n_slots

    def drop_cache(self):
        """Free the served state (before the reference runs)."""
        self.cb.cache = None
        self.cb.queue.clear()
        self.cb.slots = [None] * self.cb.n_slots
        gc.collect()

    def warm_up(self) -> None:
        """Compile (or load) every program the window will call: one
        prefill per prompt length of the mix, then one decode wave."""
        from repro.serving.scheduler import Request

        cb = self.cb
        rng = np.random.default_rng(0)
        t_all = 0.0
        for plen in self.mix["prompt_lens"]:
            req = Request(-1, rng.integers(0, self.cell.model["vocab_size"],
                                           plen).astype(np.int32), 2)
            t = clock()
            cb._prefill_slot(0, req)
            t_all += clock() - t
            cb.slots[0] = req
        t = clock()
        cb._decode_step()
        t_all += clock() - t
        self.first_call_s = t_all
        cb.slots = [None] * cb.n_slots
        if cb.stitched:
            self.stitch = stitch_summary(cb)

    # -- the window -------------------------------------------------------
    def run_window(self, traffic: Traffic, seconds: float, *,
                   trace_seconds: float = 0.0,
                   trace_dir: str | None = None) -> Window:
        """Serve ``traffic`` for ``seconds``; trace the last
        ``trace_seconds`` into ``trace_dir`` when given."""
        import jax

        win = Window()
        n_compiles = [0]

        def count(event, *_a, **_k):
            if event == "/jax/core/compile/backend_compile_duration":
                n_compiles[0] += 1
        jax.monitoring.register_event_duration_secs_listener(count)
        try:
            self._serve(win, traffic, seconds, Spans(), trace_seconds,
                        trace_dir, n_compiles)
        finally:
            jax.monitoring.unregister_event_duration_listener(count)
        if self.mix["loop"] == "open":
            win.unserved = sum(1 for r in win.reqs
                               if r.due <= win.t_close and not r.times)
        return win

    def _serve(self, win, traffic, seconds, spans, trace_seconds, trace_dir,
               n_compiles) -> None:
        import jax

        cb, mix = self.cb, self.mix
        closed = mix["loop"] == "closed"
        if closed:   # slots full from the first wave: steady state
            for i, req in enumerate(traffic.first_fill(cb.n_slots)):
                self._submit(req, None, win)
                self._prefill(i, cb.queue.popleft(), win, spans)
            win.prefills.clear()
        win.t0 = t0 = clock()
        t_end = t0 + seconds
        t_trace = t_end - trace_seconds if trace_dir else None
        n_compiles[0] = 0
        closing = None
        while True:
            now = clock()
            if t_trace is not None and now >= t_trace and not spans.on:
                jax.profiler.start_trace(trace_dir)
                spans.on = True
            if closing is None and now >= t_end:
                closing = win.t_close = now
                win.compiles = n_compiles[0]
                win.queue_at_close = len(cb.queue)
                if spans.on:
                    jax.profiler.stop_trace()
                    spans.on = False
            if closing is not None:
                if closed or self._all_started(win, closing) \
                        or now - closing > DRAIN_LIMIT_S:
                    break
            with spans("submit"):
                if closed:
                    while len(cb.queue) < mix["waiting"]:
                        self._submit(traffic.pop(), now, win)
                else:
                    while traffic.peek().due + t0 <= now:
                        req = traffic.pop()
                        self._submit(req, now, win, due=req.due + t0)
            for i in range(cb.n_slots):
                if cb.slots[i] is None and cb.queue:
                    self._prefill(i, cb.queue.popleft(), win, spans)
            active = [r for r in cb.slots if r is not None and not r.done]
            if active:
                kv = [r.pos + 1 for r in active]
                with spans("decode_wave"):
                    ts = clock()
                    cb._decode_step()
                    te = clock()
                if spans.on:
                    win.traced_waves.append(len(win.waves))
                win.waves.append((ts, te, kv))
                for r in active:
                    r.bench.times.append(te)
            with spans("host_loop"):
                for i, r in enumerate(cb.slots):
                    if r is not None and r.done:
                        r.bench.out = list(r.out)
                        win.finished.append(r.bench)
                        cb.slots[i] = None
            if not active and not cb.queue and not closed:
                with spans("idle"):
                    wait = traffic.peek().due + t0 - clock()
                    if wait > 0:
                        time.sleep(min(wait, 0.05))

    def _submit(self, req: Req, now, win: Window, due=None) -> None:
        self.cb.submit(req.prompt, max_new=req.max_new)
        self.cb.queue[-1].bench = req
        req.due = due
        if due is not None:
            win.lag_s.append(now - due)
        win.reqs.append(req)

    def _prefill(self, i, breq, win: Window, spans) -> None:
        with spans("prefill"):
            ts = clock()
            self.cb._prefill_slot(i, breq)
            te = clock()
        self.cb.slots[i] = breq
        win.prefills.append((ts, te, len(breq.prompt)))
        breq.bench.times.append(te)

    @staticmethod
    def _all_started(win: Window, closing: float) -> bool:
        return all(r.times for r in win.reqs if r.due <= closing)


def peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
