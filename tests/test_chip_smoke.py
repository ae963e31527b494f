"""``chip_smoke.py`` on the CPU: its phases at the reduced zamba2 config
(Pallas in interpret mode), its refusal to report without a TPU, and
its sharded phase on four virtual devices."""
import os
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import build_model  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    cfg = get_config(chip_smoke.MODEL).reduced()
    mdl = build_model(cfg)
    params = mdl.init(jax.random.PRNGKey(0))
    return mdl, build_model(cfg, fusion_mode="xla"), params


def test_logits_phase_matches_xla_forward(reduced):
    mdl, ref_mdl, params = reduced
    res = chip_smoke.phase_logits(mdl, ref_mdl, params, seed=0, batch=2,
                                  prompt_len=16, gen=4)
    assert res["max_rel_err"] <= chip_smoke.LOGIT_RTOL
    assert res["programs"] == 2 and res["n_groups"] > 0
    assert res["steady_s_per_wave"] is not None
    # the interpreter lowers no Mosaic kernel: the count is the chip's
    assert res["kernels_prefill"] == 0 and res["kernels_decode"] == 0


def test_serving_phase_finishes_every_request(reduced):
    mdl, _, params = reduced
    res = chip_smoke.phase_serving(
        mdl, params, seed=0, prompt_lens=(8, 20, 12, 8, 20),
        new_tokens=(3, 5, 4, 5, 3), n_slots=2, max_len=64)
    assert res["requests"] == 5
    assert res["tokens"] == 3 + 5 + 4 + 5 + 3
    assert res["programs"] >= 2


def test_check_reports_refuses_a_fallback(reduced):
    mdl, _, params = reduced
    from repro.launch.serve import _dispatch_for

    prefill, _ = _dispatch_for(mdl, stitched=True)
    toks = jax.numpy.zeros((1, 8), jax.numpy.int32)
    rep = prefill.report(params, toks, mdl.init_cache(1, 16))
    chip_smoke.check_reports("ok", [rep])
    rep.fallbacks.append((0, "patterns", "injected"))
    try:
        with pytest.raises(chip_smoke.SmokeError, match="fallback"):
            chip_smoke.check_reports("bad", [rep])
    finally:
        rep.fallbacks.pop()


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_fails_without_a_tpu(argv, capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_sharded_phase_on_four_virtual_devices(run_sharded):
    out = run_sharded(
        "import chip_smoke\n"
        "res = chip_smoke.phase_sharded(seed=0, tokens=64, d_model=256,\n"
        "                               d_ff=512)\n"
        "print('devices', res['devices'])\n"
        "print('boundaries', res['collective_boundaries'])\n"
        "print('err_ok', res['max_rel_err'] <= chip_smoke.BLOCK_RTOL)\n",
        n_devices=4)
    assert "devices 4" in out
    assert "err_ok True" in out
    assert "boundaries 0" not in out


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_directory(env_dir, monkeypatch, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    otherwise the cache sits at a fixed path inside the checkout."""
    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(cache.ENV_CACHE_DIR, raising=False)
        want = os.path.join(os.path.abspath(ROOT), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(cache.ENV_CACHE_DIR, want)
    try:
        assert os.path.abspath(cache.enable_compile_cache()) == want
        now = jax.config.jax_compilation_cache_dir
        assert now == (before if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
