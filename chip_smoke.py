#!/usr/bin/env python3
"""Prove the stitched serving path runs on a TPU through its entry points.

    python chip_smoke.py            # zamba2-1.2b at full width, one chip
    python chip_smoke.py --chips 4  # sharded stitching on a (2, 2) mesh

One chip: the model is built with ``build_model`` and random f32
parameters from ``--seed`` (as ``launch/serve.py`` builds it), then

  (a) the ``launch/serve.py`` dispatch pair prefills 4 prompts of 128
      tokens and decodes 16 tokens through the cache; every step's
      logits are compared with one full forward pass of the
      ``fusion_mode="xla"`` model over the same sequence;
  (b) a ``ContinuousBatcher`` with 4 slots answers 8 requests of mixed
      prompt lengths and 16-32 new tokens each, refilling slots
      mid-flight; every request must finish with its full token count;
  (c) each phase reports plan, compile and steady decode-wave seconds,
      its ``StitchReport`` counts and the device's peak memory.

``--chips 4`` runs only the sharded path and its comparison: a
zamba2-width MLP block through ``stitched_jit(mesh=...)`` on a
``data x model`` mesh, against the same block under ``jax.jit`` on one
device.

The run fails (non-zero exit, no result line) when the backend is not a
TPU, when a ``StitchReport`` records a fallback, a rung below
stitched/anchored or a quarantine, when a compiled stitched program
holds no Pallas kernel (``tpu_custom_call``), or when a comparison
fails.  The last line of standard output is one JSON object naming the
device.  JAX's compilation cache lives where
``$JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Logits tolerance: max |stitched - reference| <= LOGIT_RTOL * max
#: |reference|, per step.  Both sides compute in float32 with float32
#: matmuls; what differs is summation order (fused kernels, the chunked
#: scan against the one-token recurrence, the flash kernels' online
#: softmax) compounded over 38 layers -- a few 1e-6 relative per layer.
#: A bfloat16 computation errs by ~1e-2 relative and fails it.
LOGIT_RTOL = 1e-3
#: Sharded block tolerance, same rule: one f32 MLP block whose
#: down-projection sum is split across the ``model`` axis.
BLOCK_RTOL = 1e-4

MODEL = "zamba2-1.2b"


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check_reports(name: str, reports) -> dict:
    """Refuse any sign that the stitched path stepped aside; return the
    summed plan counts of ``reports``."""
    from repro.runtime.guard import RUNG_ANCHORED, RUNG_STITCHED

    reports = list(reports)
    if not reports:
        raise SmokeError(f"{name}: no stitched program was compiled")
    for rep in reports:
        if rep.fallbacks:
            raise SmokeError(f"{name}: fallback recorded: {rep.fallbacks}")
        if rep.quarantined:
            raise SmokeError(f"{name}: dispatch quarantined")
        if rep.rung not in (RUNG_ANCHORED, RUNG_STITCHED):
            raise SmokeError(f"{name}: served from rung {rep.rung!r}")
    return {"programs": len(reports),
            "plan_s": sum(r.plan_time_s for r in reports),
            "n_groups": sum(r.n_groups for r in reports),
            "n_anchored": sum(r.n_anchored for r in reports),
            "emission_reused": sum(r.emission_reused for r in reports),
            "cycle_splits": sum(r.caps_hit.get("cycle_split", 0)
                                for r in reports)}


def pallas_kernels(sf, *args) -> int:
    """``tpu_custom_call``s in the compiled program ``sf`` runs for
    ``args`` (a persistent-cache load when the call already compiled it;
    0 wherever Pallas interprets)."""
    import jax

    compiled = sf.compiled(*args)
    flat = jax.tree_util.tree_leaves((args, {}))
    return compiled._jitted.lower(*flat).compile().as_text().count(
        "tpu_custom_call")


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _block(x):
    import jax

    return jax.block_until_ready(x)


def phase_logits(mdl, ref_mdl, params, *, seed: int, batch: int = 4,
                 prompt_len: int = 128, gen: int = 16) -> dict:
    """(a) serve.py's stitched dispatch pair against one full XLA
    forward pass over the same sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import _dispatch_for
    from repro.serving.buckets import Buckets

    cfg = mdl.cfg
    V = cfg.vocab_size
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, V, (batch, prompt_len)), jnp.int32)
    # serve.generate's cache for a recurrent family: exact prompt length,
    # bucketed cache length
    max_len = Buckets.from_env().bucket(prompt_len + gen)
    cache = mdl.init_cache(batch, max_len)
    prefill, decode = _dispatch_for(mdl, stitched=True)

    t0 = time.perf_counter()
    prefill.compiled(params, prompts, cache)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    logits, new_cache = _block(prefill(params, prompts, cache))
    compile_s = time.perf_counter() - t0
    n_pre = pallas_kernels(prefill, params, prompts, cache)
    cache = new_cache
    steps = [logits[:, :, :V]]
    tok = jnp.argmax(logits[:, prompt_len - 1:prompt_len, :V], axis=-1)
    toks, wave_s, n_dec = [tok], [], 0
    for i in range(gen):
        pos = jnp.asarray(prompt_len + i)
        if i == 0:
            t0 = time.perf_counter()
            decode.compiled(params, cache, tok, pos)
            plan_s += time.perf_counter() - t0
            n_dec = pallas_kernels(decode, params, cache, tok, pos)
        t0 = time.perf_counter()
        logits, cache = _block(decode(params, cache, tok, pos))
        dt = time.perf_counter() - t0
        if i == 0:
            compile_s += dt
        else:
            wave_s.append(dt)
        steps.append(logits[:, :, :V])
        tok = jnp.argmax(logits[:, -1:, :V], axis=-1)
        toks.append(tok)

    seq = jnp.concatenate([prompts] + toks[:-1], axis=1)
    ref_fwd = jax.jit(lambda p, t: ref_mdl.apply(p, tokens=t)[0])
    ref = np.asarray(_block(ref_fwd(params, seq))[:, :, :V], np.float32)
    worst = 0.0
    for i, got in enumerate(steps):
        want = ref[:, :prompt_len] if i == 0 \
            else ref[:, prompt_len + i - 1:prompt_len + i]
        err = float(np.max(np.abs(np.asarray(got, np.float32) - want)))
        scale = float(np.max(np.abs(want)))
        if not np.isfinite(err) or err > LOGIT_RTOL * scale:
            raise SmokeError(
                f"logits step {i}: max |diff| {err:.3e} > {LOGIT_RTOL} x "
                f"max |ref| {scale:.3e}")
        worst = max(worst, err / scale)

    counts = check_reports("logits", prefill.reports() + decode.reports())
    return dict(counts, plan_s=plan_s, compile_s=compile_s,
                steady_s_per_wave=(float(np.median(wave_s))
                                   if wave_s else None),
                kernels_prefill=n_pre, kernels_decode=n_dec,
                max_rel_err=worst, tol=LOGIT_RTOL, peak_bytes=peak_bytes())


def phase_serving(mdl, params, *, seed: int,
                  prompt_lens=(32, 200, 96, 32, 200, 96, 32, 200),
                  new_tokens=(16, 32, 24, 32, 16, 24, 32, 16),
                  n_slots: int = 4, max_len: int = 512) -> dict:
    """(b) ``ContinuousBatcher`` on the stitched path: more requests
    than slots, so finished slots refill mid-flight."""
    import numpy as np

    from repro.serving.scheduler import ContinuousBatcher

    rng = np.random.default_rng(seed + 1)
    cb = ContinuousBatcher(mdl, params, n_slots=n_slots, max_len=max_len)
    want = {}
    for plen, n_new in zip(prompt_lens, new_tokens):
        prompt = rng.integers(0, mdl.cfg.vocab_size, plen).astype(np.int32)
        want[cb.submit(prompt, max_new=n_new)] = n_new
    t0 = time.perf_counter()
    results = cb.run()
    wall_s = time.perf_counter() - t0
    short = {rid: (len(results.get(rid, ())), n)
             for rid, n in want.items() if len(results.get(rid, ())) != n}
    if short:
        raise SmokeError(f"serving: requests short of tokens "
                         f"(rid: got, wanted): {short}")
    counts = check_reports(
        "serving", cb._prefill.reports() + cb._decode_wave.reports())
    st = cb.stats
    waves = st.wave_s[1:]           # the first wave compiles
    return dict(counts, requests=len(results), tokens=st.tokens_out,
                decode_waves=st.decode_waves,
                compile_s=st.compile_s - counts["plan_s"], wall_s=wall_s,
                steady_s_per_wave=(float(np.median(waves))
                                   if waves else None),
                peak_bytes=peak_bytes())


def _rmsnorm(x, g):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * g


def _mlp_block(x, g, w_up, w_down, axis: str | None = None):
    """zamba2's MLP sublayer: rmsnorm, column-parallel up-projection,
    GELU, row-parallel down-projection (summed over ``axis`` when the
    weights are sharded), the residual, and the next sublayer's
    pre-norm of it -- so the ``psum`` sits between two fused chains."""
    import jax

    y = jax.nn.gelu(_rmsnorm(x, g) @ w_up, approximate=True) @ w_down
    if axis is not None:
        y = jax.lax.psum(y, axis)
    h = x + y
    return h, _rmsnorm(h, g)


def phase_sharded(*, seed: int, tokens: int = 1024, d_model: int = 2048,
                  d_ff: int = 8192, mesh_shape=(2, 2)) -> dict:
    """Sharded stitching on a ``data x model`` mesh against the same
    block under ``jax.jit`` on one device."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.stitch import stitched_jit
    from repro.launch.mesh import make_mesh

    mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
    specs = (P("data", None), P(), P(None, "model"), P("model", None))
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    args = (jax.random.normal(key[0], (tokens, d_model), jnp.float32),
            1.0 + 0.1 * jax.random.normal(key[1], (d_model,), jnp.float32),
            jax.random.normal(key[2], (d_model, d_ff), jnp.float32)
            / np.sqrt(d_model),
            jax.random.normal(key[3], (d_ff, d_model), jnp.float32)
            / np.sqrt(d_ff))
    placed = tuple(jax.device_put(a, NamedSharding(mesh, s))
                   for a, s in zip(args, specs))
    sf = stitched_jit(functools.partial(_mlp_block, axis="model"),
                      mesh=mesh, in_specs=specs,
                      out_specs=(P("data", None), P("data", None)))
    t0 = time.perf_counter()
    sf.compiled(*placed)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = _block(sf(*placed))
    compile_s = time.perf_counter() - t0
    n_kern = pallas_kernels(sf, *placed)
    n_dev = min(len(o.sharding.device_set) for o in out)
    if n_dev != mesh.devices.size:
        raise SmokeError(f"sharded: an output spans {n_dev} devices")

    one = jax.devices()[0]
    ref = jax.jit(_mlp_block)(*(jax.device_put(a, one) for a in args))
    err = scale = 0.0
    for got, want in zip(out, ref):
        err = max(err, float(jnp.max(jnp.abs(jax.device_put(got, one)
                                             - want))))
        scale = max(scale, float(jnp.max(jnp.abs(want))))
    if not np.isfinite(err) or err > BLOCK_RTOL * scale:
        raise SmokeError(f"sharded: max |diff| {err:.3e} > {BLOCK_RTOL} x "
                         f"max |ref| {scale:.3e}")
    counts = check_reports("sharded", sf.reports())
    rep = sf.reports()[0]
    if rep.collective_boundaries < 1:
        raise SmokeError("sharded: no collective boundary was counted")
    return dict(counts, plan_s=plan_s, compile_s=compile_s,
                collective_boundaries=rep.collective_boundaries,
                devices=n_dev, kernels=n_kern, max_rel_err=err / scale,
                tol=BLOCK_RTOL, peak_bytes=peak_bytes())


def _fmt(d: dict) -> str:
    return " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in d.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # plans come from the committed code, never from a cache directory
    os.environ.pop("REPRO_PLAN_CACHE", None)

    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU backend, found {platform!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # float32 matmuls on both sides of every comparison (the TPU's
    # default f32 matmul rounds its operands to bfloat16)
    jax.config.update("jax_default_matmul_precision", "highest")
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    try:
        if args.chips == 4:
            res = phase_sharded(seed=args.seed)
            print(f"phase sharded: {_fmt(res)}", flush=True)
            if res["kernels"] < 1:
                raise SmokeError("sharded: no tpu_custom_call compiled")
        else:
            from repro.configs import get_config
            from repro.models import build_model

            cfg = get_config(MODEL)
            mdl = build_model(cfg)
            ref_mdl = build_model(cfg, fusion_mode="xla")
            t0 = time.perf_counter()
            params = _block(mdl.init(jax.random.PRNGKey(args.seed)))
            print(f"params: {MODEL} f32 seed={args.seed} "
                  f"init_s={time.perf_counter() - t0:.6g}", flush=True)
            res = phase_logits(mdl, ref_mdl, params, seed=args.seed)
            print(f"tpu_custom_call: prefill={res['kernels_prefill']} "
                  f"decode={res['kernels_decode']}", flush=True)
            print(f"phase logits: {_fmt(res)}", flush=True)
            if min(res["kernels_prefill"], res["kernels_decode"]) < 1:
                raise SmokeError("a stitched program compiled no "
                                 "tpu_custom_call")
            res = phase_serving(mdl, params, seed=args.seed)
            print(f"phase serving: {_fmt(res)}", flush=True)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
