"""Multi-device distribution tests.

jax locks the device count at first init, so these run in a child process
with XLA_FLAGS=--xla_force_host_platform_device_count=8
(tests/multidevice_child.py)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHILD = Path(__file__).parent / "multidevice_child.py"


def run_child(which: str):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(CHILD), which],
                       capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "CHILD_DONE" in r.stdout
    return r.stdout


@pytest.mark.parametrize("which", ["pipeline", "pipeline2d", "compression",
                                   "ef", "train", "serve", "elastic",
                                   "query", "store", "resilience",
                                   "relational", "d2h", "tiles",
                                   "q6_narrow", "grouped_packed"])
def test_multidevice(which):
    out = run_child(which)
    assert "OK" in out
