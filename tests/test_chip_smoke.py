"""`chip_smoke.py` end to end on the CPU at a tiny size: the platform check
is stubbed, the Pallas phase runs its kernels in interpret mode, and every
answer is still checked against ``np.searchsorted``."""
import importlib.util
import json
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_main_tiny_on_cpu(monkeypatch, capsys):
    from repro.launch import compile_cache

    chip_smoke = _load_chip_smoke()
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: jax.devices())
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    result = chip_smoke.main([], n_keys=30_000, requests=24)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == result
    assert result["ok"] is True and result["device"]["count"] == 1
    phases = [ln for ln in lines if ln.startswith("phase ")]
    assert [p.split()[2] for p in phases] == ["backend=jnp",
                                             "backend=pallas"]
    # the kernel, not its fallback, served the Pallas phase
    assert "window_fallback_queries=0 " in phases[1]


def test_chip_smoke_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        _load_chip_smoke().main([])
    assert e.value.code != 0
    assert "no TPU" in capsys.readouterr().err
