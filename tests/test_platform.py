"""What the program decides from the platform it runs on: the attention
path (compiled Pallas kernels on a TPU, XLA twins elsewhere), where the
persistent compilation cache lives, and which published peaks a roofline
share is taken against."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import get_config
from repro.core import attn_spec
from repro.launch import compile_cache, roofline


def test_attn_spec_off_tpu_takes_xla_and_interprets_kernels():
    s = attn_spec.resolve_backend(attn_spec.AttnSpec())
    assert (s.use_kernels, s.interpret) == (False, True)
    assert attn_spec.coerce(None, {}).use_kernels is False
    assert attn_spec.canonicalize(attn_spec.AttnSpec(),
                                  ("interpret",)).interpret is True
    assert get_config("deepseek_r1_671b").use_kernels is None


def test_attn_spec_on_tpu_runs_compiled_kernels(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = attn_spec.resolve_backend(attn_spec.AttnSpec())
    assert (s.use_kernels, s.interpret) == (True, False)
    assert attn_spec.canonicalize(attn_spec.AttnSpec(),
                                  ("interpret",)).interpret is False
    # explicit settings are never overridden
    forced = attn_spec.AttnSpec(use_kernels=False, interpret=True)
    assert attn_spec.resolve_backend(forced) == forced


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_left_where_the_env_puts_it(monkeypatch,
                                                  cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    assert compile_cache.enable() == path           # fixed, not per call


def test_peaks_keyed_by_device_kind():
    pk = roofline.peaks("TPU v5 lite")
    assert (pk.flops, pk.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in pk.source
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
