"""The smallthinker-21ba3b-instruct cell on the CPU, at tiny sizes (Pallas
in interpret mode): its configuration file agrees with the program's
preset, the cell loads and runs end to end against its reference and
fails on a broken timed path, the byte and operation counts of
``work_smallthinker`` match a hand count, and the new readers read
nothing off the cell."""
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
from benchlib import readers, spec, work, work_smallthinker  # noqa: E402
from benchlib.serve import Server, program_config  # noqa: E402

CELL = "smallthinker-21ba3b-instruct.window-backlog"
CONFIG = BENCH / "configs" / "smallthinker-21ba3b-instruct.json"
#: shapes small enough for the Pallas interpreter: one period of the
#: layout, an 8-position window, GQA, a share of the experts that is not
#: the first
TINY = {"n_layers": 4, "layer_pattern": "ASSS", "sliding_window": 8,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 32, "n_experts": 8, "top_k": 3, "experts_held": 3,
        "expert_offset": 2, "vocab_size": 512, "vocab_rows": 512}
READERS = ("wave_hbm_roofline.window", "decode_mfu.window",
           "prefill_attn_roofline.window")


def tiny_cell() -> spec.Cell:
    cell = spec.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY)
    cell.mix.update(slots=3, max_len=64, waiting=3, prompt_lens=[4, 8, 20],
                    prompt_weights=[1, 1, 1], block=3, check_tokens=10 ** 6,
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
        rc = run.main(["--workload", cell.name, "--seed", str(2 ** 31 + 7),
                       "--seconds", "1.5", *argv], cell=cell,
                      require_tpu=False)
    lines = buf.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return rc, last


def test_config_matches_the_preset():
    """Every key of the file's model is the preset's, but the cut: 12
    layers of the published layout, 8 of the router's 64 experts held,
    an eighth of the vocabulary."""
    from repro.configs import get_config

    conf = json.loads(CONFIG.read_text())
    cfg = program_config(conf)
    preset = get_config(conf["program_arch"])
    cut = {"n_layers", "layer_pattern", "experts_held", "vocab_size"}
    for f in dataclasses.fields(cfg):
        if f.name not in cut:
            assert getattr(cfg, f.name) == getattr(preset, f.name), f.name
    kinds = "".join("S" if s else "A" for s in conf["sliding_window_layout"])
    assert conf["rope_layout"] == conf["sliding_window_layout"]
    assert cfg.layer_pattern == kinds == "ASSS" * 3
    assert preset.layer_pattern == "ASSS" * 13
    assert cfg.n_layers == conf["num_hidden_layers"] == 12
    # the chip's share: 8 of the router's 64, top-6 kept
    assert (cfg.n_experts, cfg.top_k, cfg.n_experts_held,
            cfg.expert_offset) == (64, 6, 8, 0)
    assert cfg.n_experts_held == conf["moe_num_primary_experts"]
    assert cfg.top_k == conf["moe_num_active_primary_experts"]
    assert cfg.vocab_size == conf["vocab_size"] == 151936 // 8
    assert cfg.padded_vocab == conf["model"]["vocab_rows"] == 19200
    # every width as published
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            cfg.d_ff, cfg.sliding_window) == (
        conf["hidden_size"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"],
        conf["moe_ffn_hidden_size"], conf["sliding_window_size"])
    assert (cfg.rope_theta, cfg.norm_eps) == (conf["rope_theta"],
                                              conf["rms_norm_eps"])
    assert (cfg.position_embedding, cfg.activation, cfg.pre_attention_router,
            cfg.d_ff_shared) == ("nope", "relu", True, 0)
    assert conf["tie_word_embeddings"] is False
    assert set(conf["reduced"]) == {"num_hidden_layers", "rope_layout",
                                    "sliding_window_layout",
                                    "moe_num_primary_experts", "vocab_size"}


def test_the_harness_takes_the_cell():
    """``spec.load_cell`` finds the cell's files and metrics, and the
    generic ``work.wave_work`` that ``run.py`` counts every cell with
    reads the file without error (no Mamba layer, no shared block)."""
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["reference"] == "smallthinker_moe"
    assert {m["name"] for m in cell.end_to_end} == {"decode_tok_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) <= names
    assert {"prefill_share.backlog", "wave_ms.backlog",
            "idle_share.backlog", "plan_s", "compile_s"} <= names
    w = work.wave_work(cell.model)
    assert w.n_apps == 0 and w.state_bytes == 0
    assert cell.reference().attention_bytes_per_row(
        cell.model, cell.mix["max_len"]) < 3.5e9


def test_cell_runs_end_to_end():
    cell = tiny_cell()
    rc, out = _run(cell, "--trace", "0")
    assert rc == 0 and out is not None
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"decode_tok_s", "setup_s"}


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


HAND = {"n_layers": 2, "layer_pattern": "AS", "sliding_window": 4,
        "d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
        "d_ff": 3, "n_experts": 8, "top_k": 2, "experts_held": 4,
        "expert_offset": 0, "vocab_size": 10, "vocab_rows": 10,
        "norm_eps": 1e-6}


def test_wave_work_matches_a_hand_count():
    w = work_smallthinker.wave_work(HAND)
    # per layer: wq 4x4, wk, wv 4x2 each, wo 4x4 -> 48; router 4x8 = 32;
    # 4 held experts x 3 x 4 x 3 = 144; two norms 8 -> 232.  Final norm
    # 4, head 4 x 10 = 40.
    weights = 2 * 232 + 4 + 40
    assert w.weight_bytes == 4 * weights
    kv = [3, 7]                 # two active slots; the window is 4
    # global layer reads 3 + 7 positions, windowed 3 + 4; each writes 2
    positions = (3 + 7) + (3 + 4)
    assert w.bytes(kv) == (4 * weights                  # weights
                           + 2 * 4 * 4                  # embedding rows
                           + 2 * 10 * 4                 # logits
                           + 2 * 2 * 4 * (positions + 2 * 2))  # kv r + w
    # per token: attention 48 and router 32 per layer, 2 x 4 / 8 = 1
    # routed held expert x 36, head 40
    per_tok = 2 * (2 * (48 + 32 + 36) + 40)
    assert w.flops(kv) == 2 * per_tok + 2 * 2 * 4 * positions
    # the reference's own weights agree with the count (plus the
    # embedding table and the two norms' gains it also holds)
    ref = spec.load_module(BENCH / "refs" / "smallthinker_moe.py")
    assert ref.param_count(dict(HAND)) - 10 * 4 == weights


def test_window_prefill_work_matches_a_hand_count():
    # 6 queries, window 4: they see 1, 2, 3, 4, 4, 4 keys; 2 x 2 x hq 4
    flops, nbytes = work_smallthinker.window_prefill_work(HAND, 6)
    assert flops == 16 * (1 + 2 + 3 + 4 + 4 + 4)
    assert nbytes == (2 * 4 + 2 * 2) * 6 * 4


def _hand_view(model):
    from benchlib.serve import Window

    win = Window(t0=0.0, t_close=2.0,
                 waves=[(0.0, 1.0, [3, 5]), (1.0, 2.0, [4, 6])],
                 prefills=[(0.25, 0.75, 8)], traced_waves=[1])
    tr = type("T", (), {"span_busy_s": {"decode_wave": 0.002},
                        "window_s": 1.0, "busy_s": 0.5})()
    peak = {"hbm_bytes_per_s": 1e6, "flops_per_s": 1e9}
    mixed = dict(model, d_inner=0, ssm_state=0, ssm_head_dim=64,
                 conv_width=4)
    return readers.RunView(win=win, mix={"loop": "closed"}, model=mixed,
                           setup_s=1.0, plan_s=0.1, first_call_s=0.2,
                           work=work.wave_work(mixed), peak=peak, trace=tr)


def test_wave_readers_read_the_window_count():
    """``wave_hbm_roofline.window`` and ``decode_mfu.window`` price the
    traced waves with ``work_smallthinker``, not with ``run.work``."""
    view = _hand_view(HAND)
    w = work_smallthinker.wave_work(HAND)
    roof = spec.metric_reader(BENCH, "wave_hbm_roofline.window")
    assert roof(view) == pytest.approx(100 * w.bytes([4, 6]) / 1e6 / 0.002)
    mfu = spec.metric_reader(BENCH, "decode_mfu.window")
    assert mfu(view) == pytest.approx(100 * w.flops([4, 6]) / 1e9)
    assert w.flops([4, 6]) != view.work.flops([4, 6])


@pytest.mark.parametrize("name", READERS)
def test_new_readers_read_nothing_off_the_cell(name):
    """A run of a model with no window (the other cells', or a parent
    program's) holds nothing for the new readers; nor does an untraced
    run for the kernel's reader."""
    read = spec.metric_reader(BENCH, name)
    granite = spec.load_cell("granite-4.0-h-small.decode-moe").model
    assert read(_hand_view(granite)) is None
    if name == "prefill_attn_roofline.window":
        view = dataclasses.replace(_hand_view(HAND), trace=None)
        assert read(view) is None
