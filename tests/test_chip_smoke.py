"""chip_smoke.py off the chip: its phases rehearsed on the CPU at a reduced
width (the test, not the script, steers the device check and the model
size), the unmodified script refusing the CPU before it trains, and the
compile-cache placement its entry points share."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import repro.compile_cache as compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_rehearse_on_cpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "TRAIN_ARGS",
                        ["--arch", "paper-charlm", "--reduced",
                         "--seq-len", "8"])
    for name, goal in (("SYNC", 6), ("ASYNC", 3), ("INT8", 6)):
        args = list(getattr(chip_smoke, name))
        args[args.index("--concurrency") + 1] = "8"
        args[args.index("--aggregation-goal") + 1] = str(goal)
        monkeypatch.setattr(chip_smoke, name, args)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "(left as it is)")
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    for tag, rounds in (("sync", 3), ("async", 3), ("int8", 1)):
        assert sum(ln.startswith(f"[{tag}] round ") for ln in lines) == rounds
        assert any(ln.startswith(f"[{tag}] compile ") for ln in lines)
    assert any("kernel vs ref" in ln for ln in lines)
    assert sum(ln.startswith("[agree] eval loss") for ln in lines) == 2


def test_unmodified_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert out.stdout == ""                # no phase ran, no result line


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    was = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
