"""utils/env.py: where the compile cache goes, and what the chip can do."""

import os

import jax
import pytest

from distrifuser_tpu.utils import env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_helper_leaves_jax_config_alone_when_env_is_set(
        monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert env.setup_compile_cache() == "/somewhere/else"
    assert config_updates == []


def test_cache_helper_picks_checkout_jax_cache_when_env_is_unset(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert env.setup_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


def test_device_peaks_table():
    v5e = env.device_peaks("TPU v5 lite")
    assert (v5e.bf16_tflops, v5e.int8_tops, v5e.hbm_gbps) == (197.0, 393.0,
                                                              819.0)
    with pytest.raises(KeyError, match="no published peaks"):
        env.device_peaks("TPU v9 imaginary")
    # the attached device here is a CPU: not in the table, so an error too
    with pytest.raises(KeyError, match="no published peaks"):
        env.device_peaks()
