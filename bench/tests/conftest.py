"""The benchmark's own tests run on the CPU at toy sizes."""
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def off_chip(monkeypatch):
    """Let the harness run on the CPU: its look for a chip passes, and the
    reference takes the CPU's exact matmuls for the stated precision."""
    from bench import run

    def cpu_info(jax, chips):
        devs = jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    monkeypatch.setattr(run, "device_info", cpu_info)
    monkeypatch.setattr(run, "MATMUL", "exact")
