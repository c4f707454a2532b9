"""Launcher exit status, the device-kind spec table, and process set-up."""

import os

import pytest

from repro.hw import V5E, spec_for_device_kind
from repro.launch import process, serve
from repro.runtime import Runtime
from repro.serving.engine import ContinuousServeEngine
from repro.serving.faults import guarded_call

SERVE_ARGS = ["--arch", "tinyllama-1.1b", "--reduced", "--requests", "2",
              "--prompt-len", "4", "--max-new", "3", "--slots", "2",
              "--arrival", "all", "--engine", "continuous"]


@pytest.mark.parametrize("step_refused,status", [(True, 1), (False, 0)])
def test_serve_launcher_exit_status_follows_failed_requests(
        monkeypatch, capsys, step_refused, status):
    """A device step that always raises goes through the retry boundary
    and ends every request FAILED: the launcher prints its report and then
    exits non-zero instead of 0."""
    if step_refused:
        def refused(self, site, thunk, touched):
            def step(cancel):
                raise RuntimeError("device step refused by the compiler")
            return guarded_call(step, retries=self.max_retries,
                                backoff_s=0.0)

        monkeypatch.setattr(ContinuousServeEngine, "_dispatch", refused)
    assert serve.main(SERVE_ARGS) == status
    out = capsys.readouterr()
    assert "serve ledger:" in out.out  # the report came first either way
    assert ("FAILED" in out.out) == step_refused
    assert ("request(s) FAILED" in out.err) == step_refused


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v99 imaginary", False)])
def test_runtime_takes_the_spec_of_the_device_kind(monkeypatch, kind, known):
    import jax

    class Device:
        device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Device()])
    if known:
        assert Runtime().hw is V5E
    else:
        with pytest.raises(ValueError, match="TPU v99 imaginary"):
            Runtime()
        with pytest.raises(ValueError, match="DEVICE_SPECS"):
            spec_for_device_kind(kind)


def test_cpu_backend_keeps_the_named_v5e_target():
    assert Runtime().hw is V5E


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert process.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # sets no other
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert process.enable_compile_cache() is None  # the CPU keeps none
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        path = process.enable_compile_cache()
        assert path == str(process._CHECKOUT_CACHE)
        assert path.endswith(os.sep + ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert process.enable_compile_cache() == path  # fixed, not per run
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cpu_only_children_sets_and_restores(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with process.cpu_only_children():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with process.cpu_only_children():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ
