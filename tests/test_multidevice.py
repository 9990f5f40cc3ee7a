"""Multi-device distribution checks (subprocess: the main pytest process
must keep 1 device, as a one-chip run does)."""
import os
import subprocess
import sys

import jax
import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), "device_scripts",
                      "multidevice_checks.py")


@pytest.mark.slow
def test_multidevice_suite():
    if jax.device_count() == 1 and jax.default_backend() != "cpu":
        pytest.skip("needs multiple devices (CPU can fake 8 via XLA_FLAGS; "
                    "other single-device backends cannot)")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # 8 virtual CPU devices: the child never reaches for an accelerator
    # this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "multidevice checks failed"
    assert "FAILURES: []" in proc.stdout
