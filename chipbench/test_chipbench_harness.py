"""The chip benchmark's harness on the CPU, at tiny sizes (Pallas in
interpret mode): each cell end to end, the comparison failing on a
broken timed path, the control's reading, the trace reduction, the
byte and operation counts, the refusal off a TPU, and a closed-loop
cell of each configuration added as new files only."""
from __future__ import annotations

import copy
import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from benchlib import check, spec, trace, work  # noqa: E402
from benchlib.serve import Server  # noqa: E402

#: shapes small enough for the Pallas interpreter; every kind of layer
TINY = {"n_layers": 4, "d_model": 64, "d_inner": 128, "ssm_state": 16,
        "ssm_head_dim": 16, "vocab_size": 512, "vocab_rows": 512,
        "ssm_chunk": 16}
TINY_ATTN = {"n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
             "attn_every": 2}
CELLS = ["zamba2-1.2b.decode-short", "mamba2-370m.chat"]


def tiny_cell(name: str, root: Path = BENCH.parent,
              bench_dir: Path = BENCH) -> spec.Cell:
    cell = spec.load_cell(name, root=root, bench_dir=bench_dir)
    cell.config = copy.deepcopy(cell.config)
    model = cell.config["model"]
    model.update(TINY)
    if model.get("attn_every"):
        model.update(TINY_ATTN)
    mix = cell.mix
    # every finished request is compared, so a fault in one slot shows
    mix.update(slots=3, max_len=64, prompt_lens=[8, 16],
               prompt_weights=[1, 1], block=4, check_tokens=10 ** 6,
               check_requests=10 ** 6)
    if mix["loop"] == "closed":
        mix.update(waiting=3, output={"dist": "uniform", "lo": 4, "hi": 24})
    else:
        mix.update(rate_per_s=4.0, output={"dist": "lognormal", "median": 8,
                                           "sigma": 0.8, "lo": 2, "hi": 20})
    return cell


@pytest.fixture(autouse=True)
def _keep_precision():
    old = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", old)


def _run(cell, *argv) -> tuple[int, dict | None]:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell.name, "--seed", str(2 ** 31 + 7),
                       "--seconds", "1.5", *argv], cell=cell,
                      require_tpu=False)
    lines = buf.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return rc, last


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end(name):
    cell = tiny_cell(name)
    rc, out = _run(cell, "--trace", "0")
    assert rc == 0 and out is not None
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= \
        out["checks"]["logit_gap"]["limit"]


def test_refuses_a_cpu_backend(capsys):
    rc = run.main(["--workload", CELLS[-1], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert "{" not in capsys.readouterr().out


# -- the comparison against a broken timed path -----------------------------
@pytest.fixture(scope="module")
def served():
    cell = tiny_cell(CELLS[-1])
    # a closed loop keeps every slot busy, so the window serves enough
    cell.mix.update(loop="closed", waiting=3,
                    output={"dist": "uniform", "lo": 4, "hi": 24})
    jax.config.update("jax_default_matmul_precision", "highest")
    server = Server(cell, "stitched", None)
    server.load(3)
    server.warm_up()
    return cell, server


def _correct(served, seed) -> dict:
    cell, server = served
    server.load(seed)
    res = run.serve_once(server, cell, seed, 1.0)
    checks = run.checks_of(cell, res)
    res["correct"] = res["n_tok"] > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return res


def _state_unchanged(real, p, cache, t, q):
    logits, _ = real(p, cache, t, q)
    return logits, cache


def _half_batch(real, p, cache, t, q):
    logits, new = real(p, cache, t, q)
    half = logits.shape[0] // 2
    return logits.at[half:].set(logits[:half][:logits.shape[0] - half]), new


def _token_altered(real, p, cache, t, q):
    logits, new = real(p, cache, t, q)
    return logits.at[0].set(jnp.roll(logits[0], 1, axis=-1)), new


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_reports_not_correct(name, fault, monkeypatch):
    """A whole run (all but the look for a chip) with the decode wave
    broken underneath prints ``correct: false``."""
    warm_up = Server.warm_up

    def warm_up_then_break(self):
        warm_up(self)
        real = self.cb._decode_wave
        self.cb._decode_wave = lambda p, c, t, q: fault(real, p, c, t, q)

    monkeypatch.setattr(Server, "warm_up", warm_up_then_break)
    cell = tiny_cell(name)
    rc, out = _run(cell, "--trace", "0")
    assert rc == 0 and out is not None
    assert out["correct"] is False, out["checks"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_reading(served, monkeypatch):
    """The control (the reference with bf16_3x products) is read at the
    served positions that the check reads, and a ``--control`` run puts
    its reading in the program's place: over the limit, the run prints
    ``correct: false`` (its chip readings at the cell's size set the
    limit)."""
    cell, server = served
    res = _correct(served, 23)
    assert res["correct"] and res["n_tok"] >= 20
    m = cell.model
    toks = np.random.default_rng(0).integers(0, 512, (2, 32)).astype(
        np.int32)
    hi = server.ref.logits(m, server.cb.params, toks, "highest")
    lo = server.ref.logits(m, server.cb.params, toks, "bf16_3x")
    rel = float(jnp.max(jnp.abs(hi - lo)) / jnp.max(jnp.abs(hi)))
    assert 0 < rel < 1e-3

    # prompt positions read far off, served positions 0.5 off: only the
    # served ones count, for the program and the control alike
    ref = check.Reference(server.ref, m, server.cb.params, 32)
    req = type("R", (), {"prompt": toks[0, :8], "out": list(toks[0, 8:20])})

    def readings(toks, gather, mm="highest"):
        n, length = toks.shape
        best = np.zeros((n, length), np.float32)
        best[:, :7] = 100.0
        best[:, 7:19] = 0.5
        return best, np.zeros_like(best), np.ones((n, length), np.int32)
    monkeypatch.setattr(ref, "readings", readings)
    assert check.control_gap(ref, [req]) == (0.5, 12)
    assert check.served_gap(ref, [req]) == (0.5, 12)

    def far_control(self, reqs):
        gap, n = served_gap(self, reqs)
        return gap + 1.0, n
    served_gap = check.served_gap
    monkeypatch.setattr(check, "control_gap", far_control)
    rc, out = _run(tiny_cell(CELLS[-1]), "--trace", "0", "--control")
    assert rc == 0 and out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


# -- trace reduction ---------------------------------------------------------
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.7" } }
  event_metadata { key: 3 value { id: 3 name: "jit_wave" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 1500000000 }
    events { metadata_id: 1 offset_ps: 5500000000 duration_ps: 2500000000 }
    events { metadata_id: 3 offset_ps: 4100000000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "decode_wave" } }
  event_metadata { key: 2 value { id: 2 name: "host_loop" } }
  event_metadata { key: 3 value { id: 3 name: "some_library_event" } }
}
"""


def test_trace_reduction_on_a_small_xspace():
    from jax.profiler import ProfileData

    tr = trace.from_profile(ProfileData.from_text_proto(XSPACE))
    assert list(tr.ops) == ["/device:TPU:0"]
    assert [s[0] for s in tr.spans] == ["decode_wave", "host_loop",
                                        "decode_wave"]
    red = trace.reduce(tr)
    ms = 1e-3
    # extent 1.000..9.000 ms; ops cover 1-4 and 7-8 ms
    assert red.busy_s == pytest.approx(4 * ms)
    assert red.span_busy_s["decode_wave"] == pytest.approx(4 * ms)
    assert red.span_busy_s["host_loop"] == pytest.approx(0.0)
    assert red.span_count == {"decode_wave": 2, "host_loop": 1}
    assert red.top_ops == [["fusion.1", pytest.approx(3 * ms)],
                           ["custom-call.7", pytest.approx(2 * ms)]]
    # spans: wave 1-5 ms, host_loop 5-6.5 ms, wave 6.5-9 ms.  Idle
    # 4-7 ms has its middle (5.5) in host_loop; idle 8-9 ms in a wave
    assert dict((k, pytest.approx(v)) for k, v in red.idle_by_span) == \
        {"host_loop": 3 * ms, "decode_wave": 1 * ms}


def test_device_clock_is_shifted_into_the_program_calls():
    # ops at 0.5-2.5 and 5.5-6.5 ms on the device's clock; the host's
    # program calls span 2-5 and 7-9 ms: a shift of +1.5..+2.5 ms fits
    ms = 1e-3
    tr = trace.Trace(
        ops={"/device:TPU:0": [("a", 0.5 * ms, 2.5 * ms),
                               ("b", 5.5 * ms, 6.5 * ms)]},
        spans=[("prefill", 2 * ms, 5 * ms), ("host_loop", 5 * ms, 7 * ms),
               ("decode_wave", 7 * ms, 9 * ms)])
    off = trace.device_offset(tr, tr.ops["/device:TPU:0"])
    assert 1.5 * ms - 1e-9 <= off <= 2.5 * ms + 1e-9
    red = trace.reduce(tr)
    assert red.offset_s == pytest.approx(off)
    assert red.span_busy_s["prefill"] + red.span_busy_s["decode_wave"] == \
        pytest.approx(3 * ms)


def test_busy_intervals_merge_and_clip():
    b = trace.Busy(trace.merge([(0, 2), (1, 3), (5, 6)]))
    assert b.covered(0, 10) == pytest.approx(4)
    assert b.covered(2.5, 5.5) == pytest.approx(1.0)
    assert b.covered(3, 5) == 0


# -- bytes and operations ----------------------------------------------------
def test_wave_work_matches_a_hand_count():
    m = {"n_layers": 2, "d_model": 4, "d_inner": 8, "ssm_state": 2,
         "ssm_head_dim": 4, "conv_width": 2, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 8, "attn_every": 2, "vocab_size": 10,
         "vocab_rows": 16}
    w = work.wave_work(m)
    # one Mamba layer: in_proj 4x(16+4+2)=88, out_proj 32, norm 4,
    # conv 2x12 + 12, A/dt/D 3x2, gated norm 8 -> 174; two: 348.
    # final norm 4, head 4x10 = 40.  Shared block (applied at layer 0
    # only): wq/wk/wv 8x4 each = 96, wo 4x4 = 16, MLP 3x4x8 = 96,
    # norms 8 + 4 -> 220.
    assert w.weight_bytes == 4 * (348 + 4 + 40 + 220)
    kv = [3, 5]                       # two active slots
    state = 2 * (2 * 4 * 2 + 1 * 12)  # 2 layers x (ssm 16 + conv 12)
    assert w.bytes(kv) == (4 * (348 + 4 + 40 + 220)     # weights
                           + 2 * 4 * 4                # embedding rows
                           + 2 * 2 * state * 4        # state r+w
                           + 2 * 10 * 4               # logits
                           + 1 * 2 * 4 * 4 * (3 + 5 + 2))  # kv r + w
    # matmuls per token: 2 x (2 x (88 + 32) + 40 + 208), 1 app
    per_tok = 2 * (2 * (88 + 32) + 40 + 208)
    ssm = 2 * (6 * 2 * 4 * 2 + 2 * 2 * 12)
    attn = 1 * 2 * 2 * 4              # per cached position
    assert w.flops(kv) == 2 * (per_tok + ssm) + attn * (3 + 5)
    # the reference's own weights agree with the count
    ref = spec.load_module(BENCH / "refs" / "mamba2_stack.py")
    total = ref.param_count(m)
    assert total - 16 * 4 - 4 * (16 - 10) == 348 + 4 + 40 + 220


# -- a cell added as files only ---------------------------------------------
NEW_METRIC = '''"""Tokens per decode wave in the window."""


def read(run):
    w = run.win
    return w.tokens_in_window() / max(1, len(w.waves))
'''


@pytest.mark.parametrize("config", ["mamba2-370m", "zamba2-1.2b"])
def test_a_new_cell_needs_only_new_files(tmp_path, config):
    root = tmp_path / "checkout"
    bench_dir = root / "chipbench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads((bench_dir / "configs" / f"{config}.json").read_text())
    cfg["name"] = "tiny-ssm"
    cfg["model"].update(TINY, n_layers=2)
    if cfg["model"].get("attn_every"):
        cfg["model"].update(TINY_ATTN)
    (bench_dir / "configs" / "tiny-ssm.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "decode-short.json")
                     .read_text())
    mix.update(slots=2, max_len=48, waiting=2, prompt_lens=[12],
               prompt_weights=[1], block=2, check_tokens=10,
               check_requests=2, output={"dist": "uniform", "lo": 3,
                                         "hi": 9})
    (bench_dir / "traffic" / "tiny-backlog.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "tokens_per_wave.py").write_text(NEW_METRIC)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-ssm", "source": "test",
                             "file": "chipbench/configs/tiny-ssm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-ssm.tiny-backlog",
                               "config": "tiny-ssm",
                               "traffic": "tiny-backlog", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "tokens_per_wave", "unit": "tokens",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny-ssm.tiny-backlog"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny-ssm.tiny-backlog", root=root,
                          bench_dir=bench_dir)
    rc, out = _run(cell, "--trace", "0")
    assert rc == 0 and out["correct"] is True
    assert set(out["metrics"]) == {"tokens_per_wave", "setup_s"}
    assert out["metrics"]["tokens_per_wave"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())
