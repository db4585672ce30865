"""The training launcher's size controls and compile-cache placement."""
import dataclasses
import os

import jax
import pytest

from repro import configs
from repro.launch import train


@pytest.mark.parametrize("arch,asked,kept", [
    ("minicpm-2b", 2, 2),            # period 1: any depth
    ("recurrentgemma-2b", 5, 3),     # (rec, rec, local): whole periods
    ("recurrentgemma-2b", 1, 3),     # at least one whole period
    ("xlstm-1.3b", 17, 16),          # 7 mLSTM + 1 sLSTM per period
])
def test_num_layers_cuts_depth_only(arch, asked, kept):
    """``--num-layers`` rounds down to whole block_pattern periods and
    changes nothing but the depth."""
    args = train.parse_args(["--arch", arch, "--num-layers", str(asked)])
    cfg = train.model_config(args)
    published = configs.get_model_config(arch)
    assert cfg.num_layers == kept
    assert dataclasses.replace(cfg, num_layers=published.num_layers) \
        == published


def test_without_num_layers_the_depth_is_published():
    args = train.parse_args(["--arch", "minicpm-2b"])
    assert train.model_config(args) == configs.get_model_config(
        "minicpm-2b")


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch,
                                               cache_dir_restored):
    """A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX; the code
    sets no directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert train.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_restored):
    """Unset, the cache is the fixed ``.jax_cache`` at the checkout's
    root — the same path in every process."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = train.use_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
