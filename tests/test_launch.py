"""Launch helpers: the per-device peaks table and the compile-cache rule."""
import jax
import pytest

from repro.launch import compile_cache, roofline


def test_roofline_terms_use_the_device_peaks():
    pk = roofline.peaks("TPU v5 lite")
    terms = roofline.roofline_terms(pk["flops"], 2 * pk["hbm_bw"], 0.0,
                                    device_kind="TPU v5 lite")
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(2.0)
    assert terms["dominant"] == "memory_s"


def test_roofline_refuses_an_unknown_device_kind():
    with pytest.raises(KeyError, match="cpu"):
        roofline.roofline_terms(1.0, 1.0, 0.0, device_kind="cpu")


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch, cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
