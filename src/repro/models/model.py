"""Unified model facade over all assigned architecture families.

Families: dense, vlm (dense + vision-token stub), encoder (bidirectional),
moe, ssm (Mamba2), hybrid.  A hybrid stack is either Zamba2's (Mamba2
backbone + shared attention block every ``attn_every`` layers, weights
shared across applications, input = concat(hidden, initial embedding))
or driven by ``layer_pattern`` (Granite-4.0-H: each layer a Mamba2 or an
attention mixer with its own weights, then an FFN block of routed
experts plus a shared MLP, both sub-blocks scaled before their residual
add).

Homogeneous stacks run under ``lax.scan`` with stacked params (compile
time stays flat in depth — 95-layer deepseek lowers as one scanned
block); the hybrid stack is unrolled.  ``jax.checkpoint`` wraps the scan
body for training (activation rematerialization).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.dist.partitioning import constrain
from . import layers as L
from .layers import FusionMode


def _scan_family(cfg: ArchConfig) -> bool:
    return cfg.family in ("dense", "vlm", "encoder", "moe", "ssm")


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------
def block_init(cfg: ArchConfig, key, dtype):
    if cfg.family in ("dense", "vlm", "encoder"):
        k1, k2 = jax.random.split(key)
        return {"norm1": L.norm_init(cfg, dtype),
                "attn": L.attn_init(cfg, k1, dtype),
                "norm2": L.norm_init(cfg, dtype),
                "mlp": L.mlp_init(cfg, k2, dtype)}
    if cfg.family == "moe":
        k1, k2 = jax.random.split(key)
        return {"norm1": L.norm_init(cfg, dtype),
                "attn": L.attn_init(cfg, k1, dtype),
                "norm2": L.norm_init(cfg, dtype),
                "moe": L.moe_init(cfg, k2, dtype)}
    if cfg.family in ("ssm", "hybrid"):
        return {"norm1": L.norm_init(cfg, dtype),
                "mamba": L.mamba_init(cfg, key, dtype)}
    raise ValueError(cfg.family)


def block_apply(cfg: ArchConfig, p, h, *, fm: FusionMode, positions,
                cache=None, cache_pos=None, kv_len=None):
    """Returns (h, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if "attn" in p:
        a, c_attn = L.attn_apply(cfg, p["attn"],
                                 L.norm_apply(cfg, p["norm1"], h, fm),
                                 fm=fm, positions=positions,
                                 cache=None if cache is None else cache["attn"],
                                 cache_pos=cache_pos, kv_len=kv_len)
        h = h + a
        if "mlp" in p:
            h = h + L.mlp_apply(cfg, p["mlp"],
                                L.norm_apply(cfg, p["norm2"], h, fm), fm)
        else:
            y, aux = L.moe_apply(cfg, p["moe"],
                                 L.norm_apply(cfg, p["norm2"], h, fm), fm)
            h = h + y
        new_cache = None if cache is None else {"attn": c_attn}
    else:  # ssm
        y, c_m = L.mamba_apply(cfg, p["mamba"],
                               L.norm_apply(cfg, p["norm1"], h, fm),
                               fm=fm, cache=None if cache is None
                               else cache["mamba"], cache_pos=cache_pos)
        h = h + y
        new_cache = None if cache is None else {"mamba": c_m}
    return constrain(h, "act_btd"), new_cache, aux


def pattern_block_init(cfg: ArchConfig, kind: str, key, dtype):
    """One layer of a ``layer_pattern`` stack: ``kind`` "M" or "A"."""
    k1, k2, k3 = jax.random.split(key, 3)
    mixer = ({"attn": L.attn_init(cfg, k1, dtype)} if kind == "A" else
             {"mamba": L.mamba_init(cfg, k1, dtype)})
    return {"norm1": L.norm_init(cfg, dtype), **mixer,
            "norm2": L.norm_init(cfg, dtype),
            "moe": L.moe_share_init(cfg, k2, dtype),
            "shared_mlp": L.mlp_init(cfg, k3, dtype, d_ff=cfg.d_ff_shared)}


def pattern_block_apply(cfg: ArchConfig, p, h, *, fm: FusionMode, positions,
                        cache=None, cache_pos=None, kv_len=None):
    """norm -> mixer -> scaled residual, norm -> routed share + shared MLP
    -> scaled residual.  Returns (h, new_cache)."""
    x = L.norm_apply(cfg, p["norm1"], h, fm)
    if "attn" in p:
        y, c = L.attn_apply(cfg, p["attn"], x, fm=fm, positions=positions,
                            cache=None if cache is None else cache["attn"],
                            cache_pos=cache_pos, kv_len=kv_len)
        new_cache = None if cache is None else {"attn": c}
    else:
        y, c = L.mamba_apply(cfg, p["mamba"], x, fm=fm,
                             cache=None if cache is None else cache["mamba"],
                             cache_pos=cache_pos)
        new_cache = None if cache is None else {"mamba": c}
    h = h + y * cfg.residual_multiplier
    x = L.norm_apply(cfg, p["norm2"], h, fm)
    y = L.moe_share_apply(cfg, p["moe"], x, fm) \
        + L.mlp_apply(cfg, p["shared_mlp"], x, fm)
    return constrain(h + y * cfg.residual_multiplier, "act_btd"), new_cache


def block_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype):
    if cfg.family in ("ssm", "hybrid"):
        return {"mamba": L.mamba_cache_init(cfg, batch, dtype)}
    return {"attn": L.attn_cache_init(cfg, batch, max_len, dtype)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@dataclass
class Model:
    cfg: ArchConfig
    fusion_mode: str = "stitched"
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_unroll: int | bool = 1   # True/full for dry-run cost accounting
    remat_policy: str = "full"    # full | dots | none (see §Perf hillclimb 3)

    @property
    def fm(self) -> FusionMode:
        return FusionMode(self.fusion_mode)

    # -- params -------------------------------------------------------------
    def init(self, key) -> dict:
        cfg, dtype = self.cfg, self.param_dtype
        keys = jax.random.split(key, cfg.n_layers + 4)
        params: dict[str, Any] = {}
        if cfg.frontend == "audio":
            params["feat_proj"] = {"w": L._dense(keys[-1], cfg.frontend_dim,
                                                 cfg.d_model, dtype)}
        else:
            params["embed"] = (jax.random.normal(
                keys[-1], (cfg.padded_vocab, cfg.d_model), jnp.float32) * 0.02
            ).astype(dtype)
        params["final_norm"] = L.norm_init(cfg, dtype)
        params["lm_head"] = L._dense(keys[-2], cfg.d_model, cfg.padded_vocab, dtype)

        if _scan_family(cfg):
            params["blocks"] = jax.vmap(
                lambda k: block_init(cfg, k, dtype))(
                    jnp.stack(keys[: cfg.n_layers]))
        elif cfg.layer_pattern:  # hybrid: unrolled, each layer its own
            params["blocks"] = [pattern_block_init(cfg, kind, keys[i], dtype)
                                for i, kind in enumerate(cfg.layer_pattern)]
        else:  # hybrid: unrolled mamba list + shared attention block
            params["blocks"] = [block_init(cfg, keys[i], dtype)
                                for i in range(cfg.n_layers)]
            ka, km = jax.random.split(keys[-3])
            params["shared_attn"] = {
                "norm1": {"g": jnp.ones((2 * cfg.d_model,), dtype)},
                "attn": L.attn_init(cfg, ka, dtype, d_in=2 * cfg.d_model),
                "norm2": L.norm_init(cfg, dtype),
                "mlp": L.mlp_init(cfg, km, dtype),
            }
        return params

    # -- embedding ----------------------------------------------------------
    def _embed(self, params, tokens=None, frames=None, vision_embeds=None):
        cfg = self.cfg
        if cfg.frontend == "audio":
            h = frames.astype(self.param_dtype) @ params["feat_proj"]["w"]
        else:
            h = jnp.take(params["embed"], tokens, axis=0)
        if cfg.embedding_multiplier != 1.0:
            h = h * cfg.embedding_multiplier
        if cfg.frontend == "vision" and vision_embeds is not None:
            nv = vision_embeds.shape[1]
            h = jnp.concatenate(
                [vision_embeds.astype(h.dtype), h[:, nv:]], axis=1)
        return constrain(h, "act_btd")

    # -- forward ------------------------------------------------------------
    def apply(self, params, *, tokens=None, frames=None, vision_embeds=None,
              cache=None, cache_pos=None, kv_len=None, for_loss: bool = False):
        """Returns (logits, new_cache, aux)."""
        cfg, fm = self.cfg, self.fm
        h = self._embed(params, tokens, frames, vision_embeds)
        B, S = h.shape[:2]
        positions = (jnp.arange(S) if cache_pos is None
                     else cache_pos + jnp.arange(S))

        if _scan_family(cfg):
            def body(carry, xs):
                hh, aux = carry
                lp, lc = xs
                hh, nc, a = block_apply(cfg, lp, hh, fm=fm,
                                        positions=positions, cache=lc,
                                        cache_pos=cache_pos, kv_len=kv_len)
                return (hh, aux + a), nc

            if self.remat and cache is None and self.remat_policy != "none":
                policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                          if self.remat_policy == "dots" else None)
                body_fn = jax.checkpoint(body, policy=policy)
            else:
                body_fn = body
            (h, aux), new_cache = jax.lax.scan(
                body_fn, (h, jnp.zeros((), jnp.float32)),
                (params["blocks"], cache), unroll=self.scan_unroll)
        elif cfg.layer_pattern:  # hybrid by pattern (unrolled)
            aux = jnp.zeros((), jnp.float32)
            new_cache = {"blocks": []} if cache is not None else None
            for i in range(cfg.n_layers):
                h, nc = pattern_block_apply(
                    cfg, params["blocks"][i], h, fm=fm, positions=positions,
                    cache=None if cache is None else cache["blocks"][i],
                    cache_pos=cache_pos, kv_len=kv_len)
                if cache is not None:
                    new_cache["blocks"].append(nc)
        else:  # hybrid (unrolled)
            aux = jnp.zeros((), jnp.float32)
            emb0 = h
            new_cache = {"blocks": [], "attn": []} if cache is not None else None
            app = 0
            for i in range(cfg.n_layers):
                if cfg.attn_every and i % cfg.attn_every == 0:
                    sp = params["shared_attn"]
                    u = jnp.concatenate([h, emb0], axis=-1)
                    from repro.kernels import ops as _kops
                    u = _kops.rmsnorm(u, sp["norm1"]["g"], cfg.norm_eps,
                                      use_pallas=fm.use_pallas)
                    ac = None if cache is None else cache["attn"][app]
                    a, nc_a = L.attn_apply(cfg, sp["attn"], u, fm=fm,
                                           positions=positions, cache=ac,
                                           cache_pos=cache_pos, kv_len=kv_len)
                    h = h + a
                    h = h + L.mlp_apply(cfg, sp["mlp"],
                                        L.norm_apply(cfg, sp["norm2"], h, fm),
                                        fm)
                    if cache is not None:
                        new_cache["attn"].append(nc_a)
                    app += 1
                bc = None if cache is None else cache["blocks"][i]
                h, nc, a = block_apply(cfg, params["blocks"][i], h, fm=fm,
                                       positions=positions, cache=bc,
                                       cache_pos=cache_pos, kv_len=kv_len)
                aux = aux + a
                if cache is not None:
                    new_cache["blocks"].append(nc)

        h = L.norm_apply(cfg, params["final_norm"], h, fm)
        logits = h @ params["lm_head"]
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.padded_vocab != cfg.vocab_size:  # mask pad columns to -inf
            col = jax.lax.broadcasted_iota(jnp.int32, (cfg.padded_vocab,), 0)
            logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
        logits = constrain(logits, "logits")
        return logits, new_cache, aux

    # -- loss / train -------------------------------------------------------
    def loss(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.frontend == "audio":
            logits, _, aux = self.apply(params, frames=batch["frames"])
            labels = batch["labels"]
        else:
            tokens = batch["tokens"]
            logits, _, aux = self.apply(
                params, tokens=tokens[:, :-1],
                vision_embeds=batch.get("vision_embeds"))
            labels = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        ce = -jnp.mean(ll)
        return ce + 0.01 * aux

    # -- serving ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        cfg = self.cfg
        if _scan_family(cfg):
            one = block_cache_init(cfg, batch, max_len, dtype)
            return jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), one)
        if cfg.layer_pattern:   # KV for attention layers, else Mamba state
            return {"blocks": [
                {"attn": L.attn_cache_init(cfg, batch, max_len, dtype)}
                if kind == "A" else
                {"mamba": L.mamba_cache_init(cfg, batch, dtype)}
                for kind in cfg.layer_pattern]}
        n_apps = len([i for i in range(cfg.n_layers)
                      if cfg.attn_every and i % cfg.attn_every == 0])
        return {
            "blocks": [block_cache_init(cfg, batch, max_len, dtype)
                       for _ in range(cfg.n_layers)],
            "attn": [L.attn_cache_init(cfg, batch, max_len, dtype)
                     for _ in range(n_apps)],
        }

    def prefill(self, params, tokens=None, cache=None, **kw):
        logits, new_cache, _ = self.apply(params, tokens=tokens, cache=cache,
                                          cache_pos=0, **kw)
        return logits, new_cache

    def decode_step(self, params, cache, tokens, pos, kv_len=None, **kw):
        """tokens: [B, 1]; pos: int position of the new token."""
        logits, new_cache, _ = self.apply(params, tokens=tokens, cache=cache,
                                          cache_pos=pos, kv_len=kv_len, **kw)
        return logits, new_cache

    # -- accounting -----------------------------------------------------------
    def param_count(self) -> int:
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(shapes))

    def active_param_count(self) -> int:
        """MoE: replace expert params by the top-k active fraction."""
        cfg = self.cfg
        total = self.param_count()
        if not cfg.n_experts:
            return total
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        expert = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            if "moe/w_" in pstr or ("moe" in pstr and "w_" in pstr):
                expert += int(np.prod(leaf.shape))
        active = expert * cfg.top_k / cfg.n_experts
        return int(total - expert + active)


def build_model(cfg_or_name, fusion_mode: str = "stitched",
                param_dtype=jnp.float32, remat: bool = True,
                scan_unroll: int | bool = 1,
                remat_policy: str = "full") -> Model:
    if isinstance(cfg_or_name, str):
        from repro.configs import get_config
        cfg_or_name = get_config(cfg_or_name)
    return Model(cfg_or_name, fusion_mode, param_dtype, remat, scan_unroll,
                 remat_policy)
