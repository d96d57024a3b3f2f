"""chip_smoke.py's contract as far as a CPU can check it, and the one
compile-cache rule it reports on."""
import json
import os
import subprocess
import sys

import jax

import lightgbm_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(tmp_path, *args):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("LGBM_TPU_")}      # the smoke refuses them
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""                          # one device
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)


def test_smoke_without_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout.splitlines()[0]
    assert '"ok"' not in r.stdout


def test_smoke_cpu_rehearsal_passes_every_single_device_leg(tmp_path):
    r = _run_smoke(tmp_path, "--cpu-rehearsal")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CPU REHEARSAL" in r.stdout
    lines = r.stdout.splitlines()
    # the driver's contract: the last line holds exactly these keys
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert result["device"]["count"] == 1
    report = json.loads(lines[-2])
    assert report["rehearsal"] is True
    legs = report["legs"]
    for name in ("histogram", "train255", "train63", "reference", "predict",
                 "serve", "kernels", "cache"):
        assert isinstance(legs[name], dict), (name, legs[name])
    assert legs["fourchip"].startswith("did not run: 1 device")
    assert legs["cache"]["dir"] == str(tmp_path / "cc")


def test_compile_cache_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code;
    unset -> <checkout>/.jax_compile_cache."""
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.delenv("LGBM_TPU_NO_COMP_CACHE")   # conftest's opt-out

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    lightgbm_tpu._configure_compile_cache()
    assert "jax_compilation_cache_dir" not in updates

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    lightgbm_tpu._configure_compile_cache()
    assert updates["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_compile_cache")
