"""Batched serving driver: prefill + greedy decode with KV/SSM caches.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
      --reduced --batch 4 --prompt-len 32 --gen 16

Dispatch goes through ``stitched_jit`` unless the model was built with
``fusion_mode="xla"``; prompt and cache lengths are canonicalized onto
the serving bucket ladder, so a mix of prompt/gen lengths compiles once
per bucket instead of once per exact shape, and the jitted callables
are cached per model across ``generate`` calls (no per-call retrace).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.cache import enable_compile_cache
from repro.core.stitch import stitched_jit
from repro.models import build_model
from repro.runtime.canary import CanaryController
from repro.serving.buckets import Buckets, pad_tokens

#: per-process dispatch table: (model identity, stitched, plan_cache)
#: -> (prefill, decode).  The model object is pinned in the value so an
#: ``id()`` can never be recycled onto a stale closure.
_DISPATCH: dict[tuple, tuple] = {}


def _dispatch_for(mdl, stitched: bool, plan_cache: str | None = None):
    """The (prefill, decode) jitted pair for ``mdl`` -- cached across
    ``generate`` calls so repeated serving never retraces."""
    from repro.core.shard import ambient_mesh_key

    # a ``use_mesh`` block changes what the jitted pair compiles to
    # (GSPMD layouts + collectives), so the ambient mesh keys the table:
    # sharded serving never reuses a single-device compile or vice versa.
    key = (id(mdl), stitched, plan_cache, ambient_mesh_key())
    hit = _DISPATCH.get(key)
    if hit is not None:
        return hit[1], hit[2]

    def prefill_fn(p, t, c):
        return mdl.prefill(p, tokens=t, cache=c)

    # kv_len = pos+1 (traced) masks the unwritten cache tail exactly: a
    # static kv_len=max_len would let zero-keys inflate the softmax
    # denominator, and it is also what makes bucketed cache lengths and
    # right-padded prompts functionally inert (see serving/buckets.py).
    def decode_fn(p, c, t, pos):
        return mdl.decode_step(p, c, t, pos, kv_len=pos + 1)

    if stitched:
        # one controller for the pair: the canary overhead budget is
        # per serving process, not per dispatch callable.
        canary = CanaryController.from_env(plan_cache)
        pair = (stitched_jit(prefill_fn, plan_cache=plan_cache,
                             canary=canary),
                stitched_jit(decode_fn, plan_cache=plan_cache,
                             canary=canary))
    else:
        pair = (jax.jit(prefill_fn), jax.jit(decode_fn))
    _DISPATCH[key] = (mdl,) + pair
    return pair


def generate(mdl, params, prompts: np.ndarray, gen_len: int, *,
             greedy: bool = True, key=None, stitched: bool | None = None,
             buckets: Buckets | None = None, plan_cache: str | None = None):
    """prompts: [B, S] -> [B, S + gen_len] (greedy decode)."""
    B, S = prompts.shape
    if stitched is None:
        stitched = mdl.fusion_mode != "xla"
    bk = buckets if buckets is not None else Buckets.from_env()
    # recurrent prefill (ssm/hybrid) folds pad tokens into the state:
    # exact prompt lengths there, bucketed everywhere else.
    pad_ok = mdl.cfg.family not in ("ssm", "hybrid")
    Sp = bk.bucket(S) if pad_ok else S
    max_len = bk.bucket(max(Sp, S + gen_len))
    cache = mdl.init_cache(B, max_len)
    prefill, decode = _dispatch_for(mdl, stitched, plan_cache)

    toks_in = (jnp.asarray(pad_tokens(np.asarray(prompts, np.int32), Sp))
               if pad_ok else jnp.asarray(prompts))
    logits, cache = prefill(params, toks_in, cache)
    out = [np.asarray(prompts)]
    # the true last prompt position: causal masking hides the pad tail
    tok = jnp.argmax(logits[:, S - 1:S, : mdl.cfg.vocab_size], axis=-1)

    for i in range(gen_len):
        out.append(np.asarray(tok))
        logits, cache = decode(params, cache, tok, jnp.asarray(S + i))
        tok = jnp.argmax(logits[:, -1:, : mdl.cfg.vocab_size], axis=-1)
    return np.concatenate(out, axis=1)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--fusion", default="stitched", choices=["stitched", "xla"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")

    mdl = build_model(cfg, fusion_mode=args.fusion)
    params = mdl.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)

    t0 = time.perf_counter()
    seqs = generate(mdl, params, prompts, args.gen)
    dt = time.perf_counter() - t0
    tput = args.batch * args.gen / dt
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}: {dt:.2f}s  ({tput:.1f} tok/s incl. compile)")
    print("sample:", seqs[0, args.prompt_len - 4:].tolist())


if __name__ == "__main__":
    main()
