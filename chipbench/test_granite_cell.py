"""The granite-4.0-h-small cell on the CPU, at tiny sizes (Pallas in
interpret mode): its configuration file loads into the program's
config, the cell runs end to end against its reference and fails on a
broken timed path, and the byte and operation counts of
``work_granite`` match a hand count."""
from __future__ import annotations

import copy
import dataclasses
import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from benchlib import readers, spec, work_granite  # noqa: E402
from benchlib.serve import Server, program_config  # noqa: E402

CELL = "granite-4.0-h-small.decode-moe"
#: shapes small enough for the Pallas interpreter; both kinds of layer,
#: GQA, a share of the experts that is not the first
TINY = {"n_layers": 3, "layer_pattern": "MAM", "d_model": 64,
        "d_inner": 128, "ssm_state": 16, "ssm_head_dim": 16,
        "ssm_chunk": 16, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 32, "d_ff_shared": 48, "n_experts": 8, "top_k": 3,
        "experts_held": 3, "expert_offset": 2, "vocab_size": 512,
        "vocab_rows": 512}


def tiny_cell() -> spec.Cell:
    cell = spec.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY)
    cell.mix.update(slots=3, max_len=64, waiting=3, prompt_lens=[8, 16],
                    prompt_weights=[1, 1], block=4, check_tokens=10 ** 6,
                    check_requests=10 ** 6,
                    output={"dist": "uniform", "lo": 4, "hi": 24})
    return cell


@pytest.fixture(autouse=True)
def _keep_precision():
    old = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", old)


def _run(cell, *argv) -> tuple[int, dict | None]:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell.name, "--seed", str(2 ** 31 + 11),
                       "--seconds", "1.5", *argv], cell=cell,
                      require_tpu=False)
    lines = buf.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return rc, last


def test_program_config_reads_the_file():
    conf = json.loads((BENCH / "configs" / "granite-4.0-h-small.json")
                      .read_text())
    cfg = program_config(conf)
    m = conf["model"]
    assert cfg.family == "hybrid"
    assert cfg.layer_pattern == "MMMMMAMMMM" == "".join(
        "A" if t == "attention" else "M" for t in conf["layer_types"])
    assert cfg.n_layers == conf["num_hidden_layers"] == 10
    # the chip's share: 9 of the router's 72, top-10 kept
    assert (cfg.n_experts, cfg.top_k, cfg.n_experts_held,
            cfg.expert_offset) == (72, 10, 9, 0)
    assert cfg.n_experts_held == conf["num_local_experts"]
    assert cfg.padded_vocab == cfg.vocab_size == conf["vocab_size"] == 12544
    # every width as published
    assert (cfg.d_model, cfg.resolved_d_inner, cfg.ssm_heads,
            cfg.ssm_state) == (conf["hidden_size"],
                               conf["mamba_expand"] * conf["hidden_size"],
                               conf["mamba_n_heads"], conf["mamba_d_state"])
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim) == (
        conf["num_attention_heads"], conf["num_key_value_heads"], 128)
    assert (cfg.d_ff, cfg.d_ff_shared) == (
        conf["intermediate_size"], conf["shared_intermediate_size"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == (
        conf["embedding_multiplier"], conf["residual_multiplier"],
        conf["logits_scaling"], conf["attention_multiplier"])
    assert cfg.position_embedding == conf["position_embedding_type"]
    assert cfg.ssm_chunk == conf["mamba_chunk_size"] == m["ssm_chunk"]
    # run.py counts every cell's work with work.wave_work: its keys are
    # there, and no shared block
    assert "attn_every" not in m
    assert {"n_layers", "d_model", "d_inner", "ssm_state", "ssm_head_dim",
            "conv_width", "vocab_size"} <= set(m)


def test_cell_runs_end_to_end():
    cell = tiny_cell()
    rc, out = _run(cell, "--trace", "0")
    assert rc == 0 and out is not None
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"decode_tok_s", "setup_s"}
    assert out["checks"]["logit_gap"]["value"] <= \
        out["checks"]["logit_gap"]["limit"]


def test_broken_run_reports_not_correct(monkeypatch):
    """A token of the wave's logits rolled by one: the comparison with
    the reference fails."""
    warm_up = Server.warm_up

    def warm_up_then_break(self):
        warm_up(self)
        real = self.cb._decode_wave

        def broken(p, c, t, q):
            logits, new = real(p, c, t, q)
            return logits.at[0].set(jnp.roll(logits[0], 1, axis=-1)), new
        self.cb._decode_wave = broken

    monkeypatch.setattr(Server, "warm_up", warm_up_then_break)
    rc, out = _run(tiny_cell(), "--trace", "0")
    assert rc == 0 and out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


HAND = {"n_layers": 2, "layer_pattern": "MA", "d_model": 4, "d_inner": 8,
        "ssm_state": 2, "ssm_head_dim": 4, "conv_width": 2, "n_heads": 2,
        "n_kv_heads": 1, "head_dim": 2, "d_ff": 3, "d_ff_shared": 5,
        "n_experts": 6, "top_k": 3, "experts_held": 2, "expert_offset": 2,
        "vocab_size": 10, "vocab_rows": 10}


def test_wave_work_matches_a_hand_count():
    w = work_granite.wave_work(HAND)
    # Mamba layer: in_proj 4x(16+4+2)=88, out_proj 32, conv 2x12 + 12,
    # A/dt/D 3x2, gated norm 8 -> 170.  Attention layer: wq 4x4, wk, wv
    # 4x2 each, wo 4x4 -> 48.  Each layer's FFN block: router 4x6 = 24,
    # 2 held experts x 3 x 4 x 3 = 72, shared 3 x 4 x 5 = 60, two norms
    # 8 -> 164.  Final norm 4, head 4 x 10 = 40.
    weights = 170 + 48 + 2 * 164 + 4 + 40
    assert w.weight_bytes == 4 * weights
    kv = [3, 5]                             # two active slots
    state = 1 * (2 * 4 * 2 + 1 * 12)        # 1 Mamba layer: ssm 16, conv 12
    assert w.bytes(kv) == (4 * weights                  # weights
                           + 2 * 4 * 4                  # embedding rows
                           + 2 * 2 * state * 4          # state r + w
                           + 2 * 10 * 4                 # logits
                           + 2 * 1 * 2 * 4 * (3 + 5 + 2))  # kv r + w
    # per token: in/out_proj 120, attention 48, head 40, per layer router
    # 24 + shared 60 + 3 x 2 / 6 = 1 routed held expert x 36 = 120
    per_tok = 2 * (120 + 48 + 40 + 2 * 120)
    ssm = 6 * 2 * 4 * 2 + 2 * 2 * 12
    attn = 1 * 2 * 2 * 4                    # per cached position
    assert w.flops(kv) == 2 * (per_tok + ssm) + attn * (3 + 5)
    # the reference's own weights agree with the count (its head is the
    # embedding's transpose here only when tied)
    ref = spec.load_module(BENCH / "refs" / "granite_hybrid.py")
    assert ref.param_count(dict(HAND)) - 10 * 4 == weights


def _hand_view():
    """A closed-loop run of the hand-counted model with one traced wave,
    whose ``run.work`` is ``work.py``'s pure Mamba-2 count."""
    from benchlib.serve import Window
    from benchlib.work import wave_work

    win = Window(t0=0.0, t_close=2.0,
                 waves=[(0.0, 1.0, [3, 5]), (1.0, 2.0, [4, 6])],
                 prefills=[(0.25, 0.75, 8)], traced_waves=[1])
    tr = type("T", (), {"span_busy_s": {"decode_wave": 0.002},
                        "window_s": 1.0, "busy_s": 0.5})()
    peak = {"hbm_bytes_per_s": 1e6, "flops_per_s": 1e9}
    mixed = dict(HAND, attn_every=0)
    return readers.RunView(win=win, mix={"loop": "closed"}, model=mixed,
                           setup_s=1.0, plan_s=0.1, first_call_s=0.2,
                           work=wave_work(mixed), peak=peak, trace=tr)


def test_roofline_reads_the_granite_count():
    """``wave_hbm_roofline.moe`` prices the traced waves with
    ``work_granite``, not with ``run.work``; the cell's other readers
    are the closed-loop ``.backlog`` ones."""
    view = _hand_view()
    read = spec.metric_reader(BENCH, "wave_hbm_roofline.moe")
    want = work_granite.wave_work(view.model).bytes([4, 6]) / 1e6 / 0.002
    assert read(view) == pytest.approx(100 * want)
    assert read(dataclasses.replace(view, model={"n_layers": 1})) is None
    assert spec.metric_reader(BENCH, "idle_share.backlog")(view) == 50.0
    assert spec.metric_reader(BENCH, "wave_ms.backlog")(view) == 1e3
    assert spec.metric_reader(BENCH, "prefill_share.backlog")(view) == 25.0


def test_mfu_reads_the_granite_count():
    """``decode_mfu.moe`` counts the traced waves' operations with
    ``work_granite``, not with ``run.work``."""
    view = _hand_view()
    read = spec.metric_reader(BENCH, "decode_mfu.moe")
    flops = work_granite.wave_work(view.model).flops([4, 6])
    assert flops != view.work.flops([4, 6])
    assert read(view) == pytest.approx(100 * flops / 1.0 / 1e9)
    assert read(dataclasses.replace(view, model={"n_layers": 1})) is None
