"""Entry points: the chip smoke's phases at a tiny size on the CPU, its
refusal to run without a TPU, and where the compile cache lives."""
import importlib.util
import os

import jax
import pytest

from repro.configs import get_bundle, get_reduced
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_tiny_size(chip_smoke):
    cfg, bundle = get_reduced("granite-3-8b"), get_bundle("granite-3-8b")
    params = chip_smoke.train_phase(cfg, bundle, seq_len=64, grain_batch=1,
                                    global_batch=8, steps=3, lr=1e-3, seed=0)
    chip_smoke.serve_phase(cfg, params, batch=2, prompt_len=16, cache_len=32,
                           gen=4, seed=0)


def test_chip_smoke_refuses_a_machine_without_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.main([])
    assert capsys.readouterr().out == ""


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_an_ignored_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
