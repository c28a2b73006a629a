"""chip_smoke.py rehearsed on the CPU at a small size.

On the CPU every phase must still pass — the serving path, the stage
parity and the reference comparison all run, with the Pallas kernels in
interpret mode — and the script must end by reporting ``"ok": false`` with
exit code 1, because it found no TPU.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SMOKE = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_pass_on_cpu_and_report_not_ok(capsys, monkeypatch,
                                                        tmp_path):
    # with the variable set the script leaves JAX's cache settings alone,
    # so this in-process run writes no cache into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = _load_smoke().main(["--n", "4000"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 1
    for phase in ("device:", "build:", "stage parity:", "serve:",
                  "serve vs reference: ids identical", "serve vs float64:"):
        assert any(line.startswith(phase) for line in lines), phase
    last = json.loads(lines[-1])
    assert last == {"ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                            "count": 1}}


def test_chip_smoke_refuses_full_size_off_tpu(capsys, monkeypatch, tmp_path):
    """The default (SIFT1M) size off a TPU stops after the device line with
    exit code 1 and prints no JSON result: interpret-mode kernels at that
    size would run for hours on a host."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert _load_smoke().main([]) == 1
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    assert len(lines) == 1 and lines[0].startswith("device:")
    assert "needs the chip" in out.err


def test_chip_smoke_four_chip_phase_on_forced_host_devices(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, str(SMOKE), "--n", "4000", "--chips", "4"],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=600)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert any(line.startswith("shard_map vs vmap: ids identical")
               for line in lines), proc.stdout + proc.stderr
    assert not any(line.startswith("serve:") for line in lines)
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["count"] == 4


def test_compile_cache_placed_from_outside_or_in_the_checkout(monkeypatch,
                                                              tmp_path):
    """The entry points' cache helper: with JAX_COMPILATION_CACHE_DIR set it
    leaves JAX's setting alone; without it the cache goes to a fixed
    directory inside the checkout."""
    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        placed = compile_cache.use_compile_cache()
        assert placed == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
