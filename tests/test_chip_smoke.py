"""CPU rehearsal of ``chip_smoke.py``: its phase and kernel-check
functions run here on the smoke-width phi4 variant (the script itself
refuses to run anywhere but on a TPU), and the placement rule of the
compile cache it turns on."""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import pytest

from repro.config import A3Config, get_arch, smoke_variant
from repro.launch import compile_cache
from repro.models import decoder

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    cfg = smoke_variant(get_arch("phi4-mini-3.8b"))
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("a3,telemetry", [(A3Config(), False),
                                          (A3Config.conservative(), True)],
                         ids=["a_exact", "c_a3_telemetry"])
def test_chip_smoke_phase_serves_every_request(chip_smoke, model, a3,
                                               telemetry):
    cfg, params = model
    r = chip_smoke.serve_phase(params, cfg, a3=a3, telemetry=telemetry,
                               requests=6, prompt_len=40, max_new=5,
                               slots=3, max_len=128)
    assert r["stats"]["finished"] == 6
    assert [len(t) for t in r["tokens"]] == [5] * 6
    if telemetry:
        assert r["captured_mass"]["samples"] > 0
        assert 0.0 < r["captured_mass"]["mean"] <= 1.0


def test_chip_smoke_kernel_check_interpret(chip_smoke):
    errs = chip_smoke.check_decode_kernel(b=2, hq=6, hkv=2, d=32, s=256,
                                          interpret=True)
    assert set(errs) == {"fused", "two_pass"}
    assert max(errs.values()) <= chip_smoke.KERNEL_ATOL


@pytest.mark.parametrize("env_dir", ["placed", None],
                         ids=["env_var", "checkout_default"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(SCRIPT.parent / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        # an env-placed directory is JAX's own to read: nothing is set
        assert jax.config.jax_compilation_cache_dir == (
            before if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
