"""Entry points: failures surface as nonzero exits, one process per chip,
and the compile cache lives where it is asked to."""

import importlib.util
import logging
import pathlib

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import serve
from repro.serving.paged import PagedBackend
from repro.spatial import topology

REPO = pathlib.Path(__file__).resolve().parents[1]
SERVE_ARGS = ["--requests", "2", "--prompt-len", "16", "--max-tokens", "4",
              "--no-telemetry"]


@pytest.fixture
def no_cache_dir(monkeypatch, tmp_path):
    # JAX read JAX_COMPILATION_CACHE_DIR at import (unset), so setting it
    # now keeps enable_compile_cache() from configuring any cache here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_serve_succeeds_without_faults(no_cache_dir):
    assert serve.main(SERVE_ARGS) is None


def test_serve_exits_nonzero_on_decode_fault(no_cache_dir, monkeypatch,
                                             caplog):
    def broken(self, slots, tables, lengths):
        raise RuntimeError("decode kernel refused")

    monkeypatch.setattr(PagedBackend, "decode_step", broken)
    with caplog.at_level(logging.WARNING, "repro.serving.engine_core"):
        with pytest.raises(SystemExit) as exc:
            serve.main(SERVE_ARGS)
    assert exc.value.code not in (None, 0)
    assert "2 of 2 requests failed" in str(exc.value.code)
    assert any("decode kernel refused" in r.getMessage()
               for r in caplog.records), "fault was not logged"


def test_chip_smoke_exits_nonzero_on_cpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_compile_cache_location(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_respawn_only_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert topology.cpu_only()
    topology.require_devices(1, [])          # enough devices: no-op
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert not topology.cpu_only()
    with pytest.raises(SystemExit, match="4 shards need 4 devices"):
        topology.require_devices(4, ["-c", "pass"])
