"""``chip_smoke.py`` rehearsed on the CPU at smoke width.

The script's phases run here with the smoke configuration, the Pallas page
movers in interpret mode, and peak device memory stubbed out (the CPU
backend reports none).  What only the chip can show (compiled kernels,
full width, device memory) is left to the script's own run there.
"""

import functools
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

# Steers a fresh process the way the ``smoke`` fixture steers this one.
_STEER = """
import functools, importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs import get_smoke_config
from repro.kernels import ops as kops
for name in ("page_gather", "page_scatter", "page_compact"):
    setattr(kops, name, functools.partial(getattr(kops, name),
                                          interpret=True))
cs._peak_bytes = lambda dev: 1 << 62
cs.four_chips(get_smoke_config("qwen2.5-3b"), 0, cs._Compiles())
print("FOUR_CHIPS_OK")
"""


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), env.get("PYTHONPATH", "")])
    env.update(extra)
    return env


@pytest.fixture
def smoke(monkeypatch):
    from repro.kernels import ops as kops
    for name in ("page_gather", "page_scatter", "page_compact"):
        monkeypatch.setattr(kops, name, functools.partial(
            getattr(kops, name), interpret=True))
    mod = _load()
    monkeypatch.setattr(mod, "_peak_bytes", lambda dev: 0)
    return mod


def test_one_chip_phase_checks_pass_at_smoke_width(smoke, capsys):
    smoke.one_chip(get_smoke_config("qwen2.5-3b"), 0, smoke._Compiles())
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[")]
    assert [ln.split("]")[0] for ln in lines] == [
        "[mosaic", "[gpu-mmu", "[mosaic/xla-movers"]
    assert all(f"{smoke.N_REQUESTS} requests finished" in ln
               for ln in lines)


def test_check_failure_raises(smoke):
    with pytest.raises(RuntimeError, match="check failed: x"):
        smoke.check(False, "x")


def test_four_chip_phase_on_four_virtual_devices():
    out = subprocess.run(
        [sys.executable, "-c", _STEER.format(script=str(SCRIPT))],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_CHIPS_OK" in out.stdout
    assert out.stdout.count("decode tokens") == 4


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-repo", "script-alone"])
def test_fails_without_tpu_and_prints_no_result(alone, tmp_path):
    script = SCRIPT
    if alone:
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    out = subprocess.run([sys.executable, str(script)],
                         env=_env(PYTHONPATH=""), cwd=script.parent,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    last = (out.stdout.strip().splitlines() or [""])[-1]
    with pytest.raises(ValueError):
        json.loads(last)


def test_compile_cache_goes_where_the_environment_says(monkeypatch):
    from repro.launch import compile_cache
    seen = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert seen == []                   # left to JAX, nothing else set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    here = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == here
    assert seen == [("jax_compilation_cache_dir", here)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
