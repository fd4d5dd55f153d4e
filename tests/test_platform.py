"""Device-keyed cost profiles and the compilation-cache helper: the two
places where the program reads what machine it runs on."""
import os
from types import SimpleNamespace

import jax
import pytest

from repro.core.cost_model import (LOCAL_PC, TPU_V5E_HOST, CostModel,
                                   profile_for_device)
from repro.launch import compile_cache

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize("kind,profile", [
    ("cpu", LOCAL_PC),
    ("TPU v5 lite", TPU_V5E_HOST),
    ("TPU v5e", TPU_V5E_HOST),
])
def test_device_kind_selects_profile(kind, profile):
    assert profile_for_device(SimpleNamespace(device_kind=kind)) is profile


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no cost profile"):
        profile_for_device(SimpleNamespace(device_kind="NVIDIA H100"))


def test_cost_model_defaults_to_running_device():
    from repro.configs import get_config
    cm = CostModel.for_config(get_config("mixtral-8x7b"))
    assert cm.profile is profile_for_device(jax.devices()[0])


def test_cache_helper_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_and_ignored():
    assert compile_cache.default_cache_dir() == os.path.join(REPO,
                                                             ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
