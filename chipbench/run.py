#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a model configuration and a traffic
mix.  Weights and requests come from ``--seed``.  Set-up builds the
served model's ``ContinuousBatcher`` on the stitched path, compiles (or
loads) every program the window calls, and refuses any fallback,
quarantine or rung below stitched/anchored.  The window serves the mix
for ``--seconds``; with ``--trace 1`` its last seconds are traced and
the per-layer metrics are read, else the end-to-end ones.  Then the
served tokens of a sample of finished requests are compared with the
plain float32 reference.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` when traced) and ``checks``, each number compared beside
its limit.

It runs on a TPU only: off a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.  JAX's compilation
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.benchcache/jax`` at the checkout's root; the stitching compiler's
plan cache lives in ``.benchcache/plans``.

For manual use, not a cell: ``--fusion xla`` serves the same model
through plain ``jax.jit``; ``--control`` compares the control
(``check.control_gap``: the reference with ``bf16_3x`` products) in the
program's place, which has to print ``correct: false``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT / "src"), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / ".benchcache"
#: seconds at the end of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 5.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fusion", choices=("stitched", "xla"),
                    default="stitched")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def use_caches() -> str:
    """JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed path inside the
    checkout; the plan cache inside the checkout."""
    import jax

    jax_dir = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                   or CACHE_DIR / "jax")
    jax_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(jax_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    plans = CACHE_DIR / "plans"
    plans.mkdir(parents=True, exist_ok=True)
    return str(plans)


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def check_device(chips: int) -> str | None:
    """Why this process cannot measure the cell, or None."""
    import jax

    if jax.default_backend() != "tpu":
        return f"needs a TPU, JAX found {jax.default_backend()!r}"
    if jax.device_count() < chips:
        return f"the cell needs {chips} chips, JAX found {jax.device_count()}"
    return None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def serve_once(server, cell, seed: int, seconds: float, *,
               trace: bool = False, control: bool = False) -> dict:
    """One window and its check on loaded weights; returns the pieces of
    the result."""
    from benchlib import check
    from benchlib import trace as trace_mod
    from benchlib.serve import peak_bytes
    from benchlib.traffic import Traffic

    mix, model = cell.mix, cell.model
    traffic = Traffic(mix, seed, model["vocab_size"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    try:
        win = server.run_window(
            traffic, seconds,
            trace_seconds=min(TRACE_SECONDS, seconds) if trace else 0.0,
            trace_dir=trace_dir)
        peak = peak_bytes()
        server.drop_cache()
        reduced = trace_mod.reduce(trace_mod.load(trace_dir)) if trace \
            else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    reqs = check.sample(win.finished, seed, mix["prompt_lens"],
                        mix["check_tokens"], mix["check_requests"])
    ref = check.Reference(server.ref, model, server.cb.params,
                          mix["max_len"])
    gap, n_tok = check.served_gap(ref, reqs) if reqs else (None, 0)
    ctl = check.control_gap(ref, reqs)[0] if control and reqs else None
    del ref
    short = sum(1 for r in win.finished if len(r.out) != r.max_new)
    return {"win": win, "peak": peak, "trace": reduced,
            "gap": gap, "n_tok": n_tok,
            "n_req": len(reqs), "short": short, "control": ctl}


def checks_of(cell, res) -> dict:
    """Every number compared, beside its limit (all must be <= limit).
    With the control read, it stands in the program's place."""
    gap = res["gap"] if res["control"] is None else res["control"]
    return {"logit_gap": {"value": gap,
                          "limit": cell.config["check"]["max_logit_gap"]},
            "short_requests": {"value": res["short"], "limit": 0},
            "unserved": {"value": res["win"].unserved, "limit": 0}}


def main(argv=None, *, cell=None, require_tpu: bool = True) -> int:
    args = parse(argv)
    from benchlib import spec

    cell = cell or spec.load_cell(args.workload)
    import jax

    plan_dir = None
    if require_tpu:
        why = check_device(cell.chips)
        if why:
            log(f"chipbench: {why}; no result")
            return 2
        plan_dir = use_caches()
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"])
    dev = device_info()
    log(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")
    peak_entry = cell.peaks.get(dev["kind"])
    if peak_entry is None and require_tpu:
        log(f"chipbench: no peaks for device kind {dev['kind']!r}")
        return 2

    from benchlib import readers, work
    from benchlib.serve import Server, StitchError

    server = Server(cell, args.fusion, plan_dir)
    server.load(args.seed)
    try:
        server.warm_up()
    except StitchError as e:
        log(f"chipbench: the stitched path stepped aside: {e}")
        return 1
    if server.stitch is not None:
        s = server.stitch
        print(f"stitched: {s['programs']} programs, {s['groups']} groups, "
              f"{s['anchored']} anchored, plan-cache hits "
              f"{s['plan_cache_hits']}, rungs {','.join(s['rungs'])}; "
              "no fallback, no quarantine", flush=True)
    else:
        print(f"fusion: {args.fusion} (a manual run, not a cell)",
              flush=True)

    res = serve_once(server, cell, args.seed, args.seconds,
                     trace=bool(args.trace), control=args.control)
    win = res["win"]
    setup_s = win.t0 - T_START
    view = readers.RunView(
        win=win, mix=cell.mix, model=cell.model, setup_s=setup_s,
        plan_s=server.stitch["plan_s"] if server.stitch else None,
        first_call_s=server.first_call_s,
        work=work.wave_work(cell.model), peak=peak_entry or {},
        trace=res["trace"])
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = spec.metric_reader(cell.bench_dir, m["name"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = checks_of(cell, res)
    correct = res["n_tok"] > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    open_loop = cell.mix["loop"] == "open"
    attempted = (sum(1 for r in win.reqs if r.due <= win.t_close)
                 if open_loop else
                 sum(1 for r in win.reqs if r.times
                     and r.times[0] <= win.t_close))
    device = dict(dev, memory_peak_bytes=res["peak"])
    if args.trace:
        device.update(busy_s=res["trace"].busy_s,
                      window_s=res["trace"].window_s)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": win.unserved, "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = {"device_ops": res["trace"].top_ops,
                            "idle_gaps": res["trace"].idle_by_span}
    out["checks"] = checks

    log(f"window: {win.seconds:.6g} s, {len(win.waves)} waves, "
        f"{len(win.prefills)} prefills, {len(win.finished)} finished, "
        f"compiles inside {win.compiles}, generator lag max "
        f"{max(win.lag_s, default=0.0):.6g} s")
    log(f"compared: {res['n_req']} requests, {res['n_tok']} served tokens")
    if res["trace"] is not None:
        t = res["trace"]
        log(f"trace: {t.window_s:.6g} s, device busy {t.busy_s:.6g} s, "
            f"device clock shifted {t.offset_s * 1e3:.3g} ms, spans "
            f"{t.span_count}")
    if res["control"] is not None:
        log(f"the control (bf16_3x reference) stands in the program's "
            f"place; the program's own logit_gap {res['gap']!r}")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
