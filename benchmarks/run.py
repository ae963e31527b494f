"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig1|table2|fig7|...]
                                          [--json OUT.json]

``--json`` additionally writes every row as a structured record
(suite, name, wall-clock, plus the launch-count / HBM-saved metrics
parsed out of the derived column), so CI can archive the perf
trajectory as ``BENCH_*.json`` artifacts instead of scraping stdout.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time

#: ``key=value`` metrics embedded in a row's derived column.  Numeric
#: values keep their unit suffix out of the parsed number (``B``ytes,
#: ``us``, ``x``, ``s``).
_METRIC_RE = re.compile(r"(\w+)=(-?\d+(?:\.\d+)?(?:e-?\d+)?)(B|us|x|s)?\b")


def _row_record(suite: str, row: str) -> dict:
    name, us, derived = row.split(",", 2)
    rec: dict = {"suite": suite, "name": name, "derived": derived}
    try:
        rec["us_per_call"] = float(us)
    except ValueError:
        rec["us_per_call"] = None
    metrics: dict = {}
    for key, val, unit in _METRIC_RE.findall(derived):
        num = float(val)
        if unit == "us":
            key, num = key + "_us", num
        elif unit == "B":
            key, num = key + "_bytes", num
        elif unit == "s":
            key, num = key + "_s", num
        elif unit == "x":
            key, num = key + "_x", num
        metrics.setdefault(key, num)
    if metrics:
        rec["metrics"] = metrics
    # the headline fields the perf trajectory tracks, when present
    for want, have in (("launches", "launches"),
                       ("hbm_saved_bytes", "interpattern_hbm_saved_bytes")):
        if have in metrics:
            rec[want] = metrics[have]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["fig1", "table2", "fig7", "overhead", "roofline",
                             "plan_time", "stitch_groups", "beam_stitch",
                             "topk_tune", "serving", "guard_overhead",
                             "spmd_stitch", "canary"])
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="also write structured per-row records")
    args = ap.parse_args()

    from . import (bench_beam_stitch, bench_canary, bench_fig1_layernorm,
                   bench_fig7_speedup, bench_guard_overhead, bench_overhead,
                   bench_plan_time, bench_serving, bench_spmd_stitch,
                   bench_stitch_groups, bench_table2_breakdown,
                   bench_topk_tune, roofline)

    suites = {
        "fig1": bench_fig1_layernorm.run,
        "table2": bench_table2_breakdown.run,
        "fig7": bench_fig7_speedup.run,
        "overhead": bench_overhead.run,
        "roofline": roofline.run,
        "plan_time": bench_plan_time.run,
        "stitch_groups": bench_stitch_groups.run,
        "beam_stitch": bench_beam_stitch.run,
        "topk_tune": bench_topk_tune.run,
        "serving": bench_serving.run,
        "guard_overhead": bench_guard_overhead.run,
        "spmd_stitch": bench_spmd_stitch.run,
        "canary": bench_canary.run,
    }
    selected = [args.only] if args.only else list(suites)

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    records: list[dict] = []
    failures = 0
    for name in selected:
        try:
            for row in suites[name]():
                print(row, flush=True)
                records.append(_row_record(name, row))
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},-1,SUITE ERROR {type(e).__name__}: {e}",
                  flush=True)
            records.append({"suite": name, "name": name, "us_per_call": None,
                            "error": f"{type(e).__name__}: {e}"})
    total_s = time.perf_counter() - t0

    if args.json:
        try:
            import jax

            jax_version = jax.__version__
        except Exception:  # noqa: BLE001
            jax_version = None
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "generated_unix": time.time(),
                       "jax": jax_version, "suites": selected,
                       "failures": failures, "total_s": total_s,
                       "records": records}, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}",
              file=sys.stderr)
    print(f"# total {total_s:.1f}s", file=sys.stderr)
    if failures:  # a failed suite must fail the CI smoke step
        sys.exit(1)


if __name__ == "__main__":
    main()
