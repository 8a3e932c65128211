"""Choices the program makes from the backend it finds: Pallas interpret
mode, the hardware preset SLOs are certified against, and where compiled
programs are cached. Each is steered here by patching what JAX reports,
never through a program option."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as kernels
from repro.core.hardware import (DEVICE_KIND_PRESETS, TPU_V5E,
                                 preset_for_device_kind)
from repro.kernels import decode_attention, flash_attention, prefill_attention
from repro.launch import compile_cache, serve

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _tpu_backend(monkeypatch, kind="TPU v5 lite"):
    class Dev:
        platform, device_kind = "tpu", kind
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])


def test_interpret_mode_never_interprets_on_tpu(monkeypatch):
    assert kernels.interpret_mode() is True          # the CPU test backend
    _tpu_backend(monkeypatch)
    assert kernels.interpret_mode() is False


def test_interpret_decision_lives_in_one_function():
    """No module outside ``repro.kernels`` decides interpret mode, and no
    kernel entry point defaults to the interpreter."""
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        assert "interpret: bool = True" not in text, path
        if path != SRC / "kernels" / "__init__.py":
            assert 'default_backend() != "tpu"' not in text, path
    for mod in (decode_attention, prefill_attention, flash_attention):
        assert mod.interpret_mode is kernels.interpret_mode


def test_kernel_omitting_interpret_asks_interpret_mode(monkeypatch):
    asked = []

    def spy():
        asked.append(True)
        return True
    monkeypatch.setattr(decode_attention, "interpret_mode", spy)
    q = jnp.ones((1, 2, 8), jnp.float32)
    pages = jnp.ones((3, 4, 1, 8), jnp.float32)
    out = decode_attention.paged_decode_attention_pallas(
        q, pages, pages, jnp.zeros((1, 2), jnp.int32),
        jnp.asarray([3], jnp.int32))
    assert asked and np.all(np.isfinite(np.asarray(out)))


def test_device_kind_table():
    assert preset_for_device_kind("TPU v5 lite") is TPU_V5E
    assert all(p.name in serve.PRESETS for p in DEVICE_KIND_PRESETS.values())
    with pytest.raises(ValueError, match="no hardware preset"):
        preset_for_device_kind("TPU v9 imaginary")


def test_serve_takes_the_preset_of_the_tpu_present(monkeypatch):
    assert serve.resolve_hardware(None).name == "a10"        # CPU backend
    assert serve.resolve_hardware("tpu_v5e") is TPU_V5E
    _tpu_backend(monkeypatch)
    assert serve.resolve_hardware(None) is TPU_V5E
    _tpu_backend(monkeypatch, kind="TPU v9 imaginary")
    with pytest.raises(ValueError):
        serve.resolve_hardware(None)


def test_cache_dir_follows_env_else_fixed_repo_path(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.compile_cache_dir() == str(repo / ".jax_cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_enable_compile_cache_sets_only_the_fixed_path(monkeypatch, tmp_path):
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: set_to.append((name, val)))
    assert compile_cache.enable_compile_cache() is None      # CPU: off
    assert set_to == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert set_to == []                       # JAX reads the variable itself
    monkeypatch.delenv(compile_cache.ENV_VAR)
    path = compile_cache.enable_compile_cache()
    assert set_to == [("jax_compilation_cache_dir", path)]
    assert path == str(compile_cache.REPO_CACHE_DIR)
