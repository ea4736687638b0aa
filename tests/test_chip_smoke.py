"""chip_smoke.py on the CPU, in process: the device gate refuses to
run, and every phase passes at the rehearsal sizes."""
import importlib.util
import json
import sys
from pathlib import Path

import jax
import pytest

from repro.api.facade import FastVAT
from repro.launch import compile_cache

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)  # for dataclasses
    spec.loader.exec_module(mod)
    # the smoke run would point this process's compile cache into the
    # checkout; the helper has its own test below
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    return mod


def _last_json(out: str):
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_gate_refuses_without_tpu(smoke, monkeypatch, capsys):
    def no_fit(*a, **k):
        raise AssertionError("the gate let a fit through")
    monkeypatch.setattr(FastVAT, "fit", no_fit)
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert _last_json(out.out) is None
    assert "refusing" in out.err


def test_rehearsal_phases_pass(smoke, capsys):
    assert smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    for phase in ("exact/default", "exact/pallas", "oracle",
                  "embedding/default", "embedding/pallas", "serve"):
        assert f"[phase {phase}] ok" in out, out
    last = out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_four_chips_needs_four_devices(smoke, capsys):
    if len(jax.devices()) >= 4:
        pytest.skip("this process sees four devices")
    assert smoke.main(["--rehearse", "--four-chips"]) == 1
    out = capsys.readouterr().out
    assert "[phase four-chips/sharded-vs-solo] FAILED" in out
    assert _last_json(out) is None


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, env):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves the cache to
    JAX; without it the cache goes to the fixed in-checkout path."""
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    try:
        got = compile_cache.use_compile_cache()
        if env is None:
            assert got == str(compile_cache.DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            assert compile_cache.DEFAULT_DIR.parent == _PATH.parent
        else:
            assert got == str(tmp_path / env)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
