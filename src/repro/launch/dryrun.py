import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the
device count at first init): they create 512 host placeholder devices so
``make_production_mesh`` can build the 16x16 single-pod and 2x16x16
multi-pod meshes.  Do not set this flag anywhere else — smoke tests and
benches see the real single device.

Per cell this script:
  1. builds model + optimizer ShapeDtypeStructs (no allocation),
  2. jits the step with NamedSharding in/out shardings,
  3. ``.lower().compile()`` — success proves the sharding config is
     coherent (no sharding mismatch / unsupported collective / comp OOM),
  4. records ``memory_analysis()`` / ``cost_analysis()`` / collective
     bytes parsed from the HLO for EXPERIMENTS.md §Dry-run + §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import ARCH_IDS, SHAPES, cell_applicable, get_config
from repro.dist import partitioning
from repro.dist.partitioning import param_specs
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import build_model

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)"
                       r"\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", ls)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for c in _COLLECTIVES:
            if re.search(rf"\b{c}(-start|-done)?\(", rhs) or \
               re.search(rf"\)\s*{c}\b", rhs):
                op = c
                break
        if op is None:
            continue
        if f"{op}-done" in rhs:
            continue  # counted at -start
        # result shape(s) are at the start of the rhs, before the op name
        head = rhs.split(f" {op}")[0] if f" {op}" in rhs else rhs
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(head):
            size = int(np.prod([int(d) for d in dims.split(",") if d])) \
                if dims else 1
            nbytes += size * _DTYPE_BYTES[dt]
        out[op] += nbytes
    return out


def _shardings(mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda s: isinstance(s, P))


def _lower_and_compile(cfg, mdl, cell, mesh, *, zero1: bool,
                       bf16_grads: bool, moe_ep: str = "model",
                       microbatches: int = 1, sp_model: bool = False):
    """Build the right step fn for the cell and lower+compile it."""
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(0)
    params_struct = jax.eval_shape(mdl.init, key)
    pspecs = param_specs(params_struct, mesh, moe_ep=moe_ep)
    param_sh = _shardings(mesh, pspecs)
    batch_struct = S.batch_specs(cfg, cell)
    batch_sh = _shardings(mesh, S.batch_pspecs(cfg, cell, mesh))
    seq_sharded = cell.global_batch == 1

    with partitioning.use_mesh(mesh, seq_sharded=seq_sharded, moe_ep=moe_ep,
                               kv_seq=S.kv_seq_axes(cfg, cell, mesh),
                               sp_model=sp_model):
        if cell.kind == "train":
            opt_cfg = optim.AdamWConfig(bf16_grads=bf16_grads)
            opt_struct = jax.eval_shape(
                lambda p: optim.init(opt_cfg, p), params_struct)
            opt_sh = _shardings(mesh, S.opt_pspecs(
                pspecs, zero1=zero1, params_struct=params_struct))
            step = S.make_train_step(mdl, opt_cfg, microbatches=microbatches)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh,
                               _shardings(mesh, S.metric_pspecs())),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_struct, opt_struct, batch_struct)
        elif cell.kind == "prefill" and cfg.family == "encoder":
            step = S.make_encoder_step(mdl)
            jitted = jax.jit(step, in_shardings=(param_sh, batch_sh),
                             out_shardings=None)
            lowered = jitted.lower(params_struct, batch_struct)
        elif cell.kind == "prefill":
            cache_struct = jax.eval_shape(
                lambda: mdl.init_cache(cell.global_batch, cell.seq_len,
                                       jnp.bfloat16))
            cache_sh = _shardings(
                mesh, S.cache_pspecs(cfg, cell, mesh, cache_struct))
            step = S.make_prefill_step(mdl)
            jitted = jax.jit(step,
                             in_shardings=(param_sh, batch_sh, cache_sh),
                             out_shardings=None, donate_argnums=(2,))
            lowered = jitted.lower(params_struct, batch_struct, cache_struct)
        else:  # decode
            cache_struct = jax.eval_shape(
                lambda: mdl.init_cache(cell.global_batch, cell.seq_len,
                                       jnp.bfloat16))
            cache_sh = _shardings(
                mesh, S.cache_pspecs(cfg, cell, mesh, cache_struct))
            step = S.make_decode_step(mdl, kv_len=cell.seq_len)
            pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(step,
                             in_shardings=(param_sh, cache_sh,
                                           batch_sh["tokens"],
                                           NamedSharding(mesh, P())),
                             out_shardings=None, donate_argnums=(1,))
            lowered = jitted.lower(params_struct, cache_struct,
                                   batch_struct["tokens"], pos_struct)

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    return compiled, t_lower, t_compile


def _cost_of(compiled) -> dict:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", -1)) if cost else -1.0,
        "bytes_accessed": float(cost.get("bytes accessed", -1)) if cost else -1.0,
        "collective_bytes": coll,
        "collective_total": int(sum(coll.values())),
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             fusion_mode: str = "xla", zero1: bool = True,
             bf16_grads: bool = True, verbose: bool = True,
             extrapolate: bool = True, extra_tags: str = "",
             overrides: dict | None = None, moe_ep: str | None = None,
             remat_policy: str = "full", microbatches: int = 1,
             sp_model: bool | None = None) -> dict:
    """Dry-run one (arch x shape x mesh) cell.

    Two-phase cost accounting (XLA's cost_analysis counts a while-loop
    body ONCE, so scanned-layer costs are wrong by ~n_layers):
      phase 1: FULL depth, scanned -- the compile/sharding proof and the
               memory analysis (this is the deliverable-(e) artifact);
      phase 2: unrolled 1-layer and 2-layer models -- exact per-layer
               costs, linearly extrapolated to full depth:
               total = f(1) + (L-1) * (f(2) - f(1)).
    The hybrid family is a python-unrolled stack, so phase 1 already
    yields exact costs and phase 2 is skipped.
    """
    import dataclasses as _dco
    cfg = get_config(arch)
    if overrides:
        cfg = _dco.replace(cfg, **overrides)
    if moe_ep is None:
        moe_ep = getattr(cfg, "moe_ep", "model")
    cell = SHAPES[shape_name]
    if sp_model is None:
        # Megatron-SP default for batch>1 train/prefill: norms/ew shard S
        # over TP (bytes -1.2x..-11.7x across families; §Perf hillclimb 3)
        sp_model = cell.kind in ("train", "prefill") and cell.global_batch > 1
    ok, why = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    result = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(map(str, mesh.devices.shape)),
              "multi_pod": multi_pod, "fusion_mode": fusion_mode,
              "kind": cell.kind, "tags": extra_tags}

    try:
        # phase 1: full-depth compile proof (scan) + memory analysis
        mdl = build_model(cfg, fusion_mode=fusion_mode,
                          param_dtype=jnp.bfloat16,
                          remat=(cell.kind == "train"), scan_unroll=1,
                          remat_policy=remat_policy)
        compiled, t_lower, t_compile = _lower_and_compile(
            cfg, mdl, cell, mesh, zero1=zero1, bf16_grads=bf16_grads,
            moe_ep=moe_ep, microbatches=microbatches, sp_model=sp_model)
        result.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "n_devices": int(np.prod(mesh.devices.shape)),
            "params": mdl.param_count(),
            "active_params": mdl.active_param_count(),
            **{f"scanned_{k}": v for k, v in _cost_of(compiled).items()},
        })
        mem = compiled.memory_analysis()
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "alias_size_in_bytes",
                         "generated_code_size_in_bytes"):
                val = getattr(mem, attr, None)
                if val is not None:
                    result[attr] = int(val)

        # phase 2: exact per-layer cost via 1- and 2-layer unrolled models
        import dataclasses as _dc
        if cfg.family == "hybrid" or not extrapolate:
            for k in ("flops", "bytes_accessed", "collective_total"):
                result[k] = result[f"scanned_{k}"]
            result["cost_method"] = "exact(unrolled)"
        else:
            costs = {}
            for L in (1, 2):
                cfgL = _dc.replace(cfg, n_layers=L)
                mdlL = build_model(cfgL, fusion_mode=fusion_mode,
                                   param_dtype=jnp.bfloat16,
                                   remat=(cell.kind == "train"),
                                   scan_unroll=True,
                                   remat_policy=remat_policy)
                cL, _, _ = _lower_and_compile(cfgL, mdlL, cell, mesh,
                                              zero1=zero1,
                                              bf16_grads=bf16_grads,
                                              moe_ep=moe_ep,
                                              microbatches=microbatches,
                                              sp_model=sp_model)
                costs[L] = _cost_of(cL)
            L = cfg.n_layers
            for k in ("flops", "bytes_accessed", "collective_total"):
                per_layer = costs[2][k] - costs[1][k]
                result[k] = costs[1][k] + (L - 1) * per_layer
                result[f"{k}_per_layer"] = per_layer
            result["collective_bytes"] = {
                c: costs[1]["collective_bytes"][c] + (L - 1) *
                   (costs[2]["collective_bytes"][c]
                    - costs[1]["collective_bytes"][c])
                for c in costs[1]["collective_bytes"]}
            result["cost_method"] = "extrapolated(L1,L2 unrolled)"

        if verbose:
            print(f"[ok] {arch} x {shape_name} mesh={result['mesh']} "
                  f"flops={result['flops']:.3e} "
                  f"coll={result.get('collective_total', 0):.3e}B "
                  f"compile={t_compile:.1f}s", flush=True)
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        result.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[ERR] {arch} x {shape_name}: {e}", flush=True)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all arch x shape")
    ap.add_argument("--fusion", default="xla", choices=["xla", "stitched"])
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    pods = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                res = run_cell(arch, shape, multi_pod=mp,
                               fusion_mode=args.fusion)
                results.append(res)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(
                            {k: v for k, v in res.items()
                             if k != "traceback"}) + "\n")

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    if n_err:
        for r in results:
            if r["status"] == "error":
                print(f"  FAILED {r['arch']} x {r['shape']}: {r['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
