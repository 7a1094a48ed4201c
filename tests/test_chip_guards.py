"""Guards of the chip path that a CPU can check: the bring-up script
refuses to run without a TPU, the device table refuses unknown kinds,
engines on an accelerator clock the detected chip unscaled, the compile
cache sits where the environment or the checkout says, ``--full`` serves
the published config, and the jitted param init matches the eager one."""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.hardware import (TPU_V5E, TPU_V5P, chip_for_device_kind,
                                 relative_speed)
from repro.launch import compile_cache
from repro.launch import serve
from repro.models import transformer as T
from repro.serving import engine as engine_mod
from repro.serving.backends import init_real_params

ROOT = Path(__file__).resolve().parents[1]
CFG = get_smoke_config("qwen2.5-3b")


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _refuse(*a, **k):
    raise AssertionError("reached past the device check")


def test_chip_smoke_exits_nonzero_without_tpu(monkeypatch, capsys):
    chip_smoke = _load_chip_smoke()
    monkeypatch.setattr(engine_mod.Engine, "__init__", _refuse)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", _refuse)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr()
    assert "no TPU found" in out.err
    assert out.out == ""            # no phase line, no verdict


@pytest.mark.parametrize("kind,chip", [("TPU v5 lite", TPU_V5E),
                                       ("TPU v5", TPU_V5P)])
def test_device_kind_maps_to_chip(kind, chip):
    assert chip_for_device_kind(kind) is chip


@pytest.mark.parametrize("kind", ["TPU v7x", "TPU v6 lite", "cpu", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="unknown device kind"):
        chip_for_device_kind(kind)


def test_compile_cache_honours_environment(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []            # JAX reads its own variable


def test_compile_cache_defaults_to_one_checkout_path(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.compile_cache_dir()
    assert compile_cache.enable_compile_cache() == first
    assert compile_cache.compile_cache_dir() == first
    assert Path(first) == ROOT / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", first)]


def test_engine_on_accelerator_clocks_detected_chip(monkeypatch, params):
    monkeypatch.setattr(engine_mod, "local_chip", lambda: TPU_V5P)
    eng = engine_mod.Engine(0, CFG, params, slots=2, capacity=32)
    assert eng.hardware == TPU_V5P.name
    assert eng.speed_factor == 1.0 and eng.capacity_weight == 1.0
    same = engine_mod.Engine(1, CFG, params, slots=2, capacity=32,
                             chip=TPU_V5P)
    assert same.speed_factor == 1.0
    with pytest.raises(ValueError, match="not rescaled"):
        engine_mod.Engine(2, CFG, params, slots=2, capacity=32, chip=TPU_V5E)
    with pytest.raises(ValueError, match="not rescaled"):
        engine_mod.Engine(3, CFG, params, slots=2, capacity=32,
                          speed_factor=2.0)


def test_engine_on_cpu_keeps_scaled_clock(params):
    eng = engine_mod.Engine(0, CFG, params, slots=2, capacity=32,
                            chip=TPU_V5P)
    assert eng.speed_factor == pytest.approx(1 / relative_speed(TPU_V5P))


def test_serve_on_accelerator_defaults_to_detected_chip(monkeypatch,
                                                        capsys):
    for mod in (serve, engine_mod):
        monkeypatch.setattr(mod, "local_chip", lambda: TPU_V5P)
    serve.main(["--requests", "2", "--isl", "8", "--osl", "2"])
    out = json.loads(capsys.readouterr().out)
    assert {h for pool in out["hardware"].values() for h in pool} == \
        {TPU_V5P.name}
    with pytest.raises(ValueError, match="not rescaled"):
        serve.main(["--decode-chip", "v5e", "--requests", "1"])


def test_serve_full_selects_published_config(monkeypatch, capsys):
    assert serve.model_config("qwen2.5-3b", True) is get_config("qwen2.5-3b")
    assert serve.model_config("qwen2.5-3b", False) is CFG
    monkeypatch.setattr(serve, "init_real_params", _refuse)
    serve.main(["--full", "--backend", "sim", "--requests", "2",
                "--isl", "16", "--osl", "2"])
    out = json.loads(capsys.readouterr().out)
    assert out["arch"] == "qwen2.5-3b" and out["completed"] == 2


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "rwkv6-1.6b"])
def test_jitted_param_init_matches_eager(arch):
    cfg = get_smoke_config(arch)
    jitted = jax.tree.leaves(init_real_params(cfg, 0))
    eager = jax.tree.leaves(T.init_params(cfg, jax.random.PRNGKey(0)))
    assert len(jitted) == len(eager)
    for a, b in zip(jitted, eager):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8))
